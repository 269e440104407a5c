"""Multi-device semantics via 8-fake-device subprocesses."""
import pytest

from conftest import run_multidevice

pytestmark = pytest.mark.distributed


@pytest.mark.slow
def test_distributed_count_matches_single():
    out = run_multidevice("""
import jax, numpy as np
mesh = jax.make_mesh((2, 4), ("data", "model"))
from repro.graphs import kronecker_rmat
from repro.core import count_triangles, count_triangles_distributed
e = kronecker_rmat(10, seed=3)
a = count_triangles(e)
b = count_triangles_distributed(e, mesh)
assert a == b, (a, b)
print("OK", a)
""")
    assert "OK" in out


@pytest.mark.slow
def test_distributed_count_shorter_side_variant_exact():
    out = run_multidevice("""
import jax
mesh = jax.make_mesh((2, 4), ("data", "model"))
from repro.graphs import kronecker_rmat, watts_strogatz
from repro.core import count_triangles, count_triangles_distributed
for e in [kronecker_rmat(10, seed=3), watts_strogatz(2000, 12, 0.2, seed=1)]:
    a = count_triangles(e)
    b = count_triangles_distributed(e, mesh, shorter_side=True)
    assert a == b, (a, b)
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_sharded_lm_train_step_matches_single_device():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import REGISTRY
from repro.configs.lm_common import make_lm_train_step, _rules_for
from repro.distributed.sharding import make_param_shardings, spec_for
from repro.models import transformer as tfm
from repro.data import lm_batch

from repro.launch.mesh import make_local_mesh

cfg = REGISTRY["qwen2-1.5b"].smoke_config()
mesh = make_local_mesh(2, 4)  # auto-sharded axes, as the launchers build them
params = tfm.init_params(jax.random.PRNGKey(0), cfg)
step_fn, opt_init = make_lm_train_step(cfg, accum=2)
opt = opt_init(params)
raw = lm_batch(0, 0, 8, 32, cfg.vocab_size)
batch = {k: jnp.asarray(v).reshape(2, 4, 32) for k, v in raw.items()}

p1, o1, m1 = jax.jit(step_fn)(params, opt, batch)

rules = _rules_for(cfg, mesh)
psh = make_param_shardings(mesh, rules, params)
osh = jax.tree.map(lambda s: NamedSharding(mesh, s), spec_for(rules, opt))
bsh = {k: NamedSharding(mesh, P(None, "data", None)) for k in batch}
with mesh:
    sharded = jax.jit(step_fn, in_shardings=(psh, osh, bsh))
    p2, o2, m2 = sharded(jax.device_put(params, psh), jax.device_put(opt, osh),
                         jax.device_put(batch, bsh))
# The loss *metric* reduction reorders under GSPMD on some jax versions
# (observed 0.019 absolute on jax 0.4.37 CPU) while grads/params stay
# bit-close; params below are the strict equivalence check.  The bound
# leaves little headroom over the observed drift so real metric
# regressions (≳1%) still fail.
assert abs(float(m1["loss"]) - float(m2["loss"])) < 2.5e-2, (m1["loss"], m2["loss"])
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)
print("OK", float(m2["loss"]))
""")
    assert "OK" in out


@pytest.mark.slow
def test_compressed_psum_and_error_feedback():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from repro.distributed.compression import compress_grads, make_error_feedback_state

mesh = jax.make_mesh((8,), ("data",))
g = {"w": jnp.arange(32.0).reshape(8, 4) / 7.0}
ef = make_error_feedback_state({"w": g["w"][0]})

def body(g_shard, e_shard):
    gs = {"w": g_shard.reshape(4)}
    es = {"w": e_shard.reshape(4)}
    sync, new_e = compress_grads(gs, es, "data")
    return sync["w"], new_e["w"]

f = shard_map(body, mesh=mesh, in_specs=(P("data"), P(None)),
              out_specs=(P(None), P(None)), check_vma=False)
sync, new_e = f(g["w"].reshape(32), ef["w"])
exact = np.asarray(g["w"]).reshape(8, 4).mean(0)
got = np.asarray(sync)
rel = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
assert rel < 0.02, (got, exact)
print("OK", rel)
""")
    assert "OK" in out


@pytest.mark.slow
def test_elastic_restore_to_different_mesh():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager

tree = {"w": jnp.arange(64.0).reshape(8, 8)}
with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d, async_save=False)
    mgr.save(5, tree)
    # restore onto a 2×4 mesh then a 4×2 mesh (elastic restart)
    for shape in [(2, 4), (4, 2)]:
        mesh = jax.make_mesh(shape, ("data", "model"))
        sh = {"w": NamedSharding(mesh, P("data", "model"))}
        got, step, _ = mgr.restore_latest(tree, shardings=sh)
        assert step == 5
        np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
        assert got["w"].sharding.mesh.devices.shape == shape
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_gnn_edge_partitioned_matches_replicated():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import REGISTRY
from repro.graphs import erdos_renyi

mod = REGISTRY["gcn-cora"]
cfg = mod.smoke_config()
model = mod.MODEL
e = erdos_renyi(40, 160, seed=0)
n = int(e.max()) + 1
rng = np.random.default_rng(0)
feat = jnp.asarray(rng.normal(size=(n, cfg.d_in)).astype(np.float32))
params = model.init_params(jax.random.PRNGKey(0), cfg)
pad = (-e.shape[0]) % 8
src = jnp.asarray(np.concatenate([e[:,0], -np.ones(pad)]).astype(np.int32))
dst = jnp.asarray(np.concatenate([e[:,1], -np.ones(pad)]).astype(np.int32))
single = model.apply(params, cfg, feat, None, src, dst)
mesh = jax.make_mesh((8,), ("data",))
with mesh:
    f = jax.jit(lambda p, x, s, d: model.apply(p, cfg, x, None, s, d),
                in_shardings=(None, None, NamedSharding(mesh, P("data")),
                              NamedSharding(mesh, P("data"))))
    sharded = f(params, feat, src, dst)
np.testing.assert_allclose(np.asarray(single), np.asarray(sharded), rtol=1e-4, atol=1e-4)
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_gcn_shardmap_psum_matches_single_device():
    out = run_multidevice("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import AxisType, PartitionSpec as P
from repro.models.gnn import gcn
from repro.graphs import erdos_renyi
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
e = erdos_renyi(48, 200, seed=0); n = int(e.max())+1
pad = (-e.shape[0]) % 8
src = jnp.asarray(np.concatenate([e[:,0], -np.ones(pad)]).astype(np.int32))
dst = jnp.asarray(np.concatenate([e[:,1], -np.ones(pad)]).astype(np.int32))
cfg = gcn.GCNConfig(d_in=12, d_hidden=16, d_out=5, smart_order=True)
cfg_ps = dataclasses.replace(cfg, psum_axes=("data",))
p = gcn.init_params(jax.random.PRNGKey(0), cfg)
feat = jax.random.normal(jax.random.PRNGKey(1), (n, 12))
single = gcn.apply(p, cfg, feat, None, src, dst)
f = shard_map(lambda p, x, s, d: gcn.apply(p, cfg_ps, x, None, s, d),
              mesh=mesh, in_specs=(P(), P(), P("data"), P("data")),
              out_specs=P(), check_vma=False)
with jax.set_mesh(mesh):
    sharded = jax.jit(f)(p, feat, src, dst)
np.testing.assert_allclose(np.asarray(single), np.asarray(sharded), rtol=2e-4, atol=2e-4)
g = jax.grad(lambda p: jnp.sum(f(p, feat, src, dst)**2))(p)
assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_distributed_panel_schedule_exact():
    out = run_multidevice("""
import jax
mesh = jax.make_mesh((2, 4), ("data", "model"))
from repro.graphs import kronecker_rmat
from repro.core import count_triangles
from repro.core.distributed import count_triangles_distributed_panel
e = kronecker_rmat(10, seed=3)
a = count_triangles(e)
b = count_triangles_distributed_panel(e, mesh, widths=(16, 64, 256, 1024))
assert a == b, (a, b)
print("OK", a)
""")
    assert "OK" in out
