"""Paper §III-E: multi-device counting + Amdahl split.

Reports per-phase times and the preprocessing fraction that bounds
multi-device speedup (the paper measures 0.08–0.76 across graphs).
Beyond the global count, the striped backend carries every engine
workload, so the table also times per-node, per-edge support and a full
truss decomposition striped vs single-device — each row
identity-asserted against the wedge schedule before it is reported (a
fast wrong kernel scores zero).

On an accelerator host the suite runs in-process on the real devices
(a chip belongs to one process, so no child is started) and needs at
least two of them.  On the CPU backend it runs in a child with 8 fake
CPU devices.  Every row names the devices it ran on, and any failure
raises — a failed phase never becomes a row.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHILD = "import json; from benchmarks.multidevice import measure; print(json.dumps(measure()))"


def measure() -> dict:
    """Time every phase on a mesh over all of this process's devices."""
    import jax
    import jax.numpy as jnp

    from repro.analytics.truss import k_truss_decomposition
    from repro.core import (
        TriangleCounter,
        count_triangles,
        count_triangles_distributed,
        preprocess,
    )
    from repro.graphs import kronecker_rmat, watts_strogatz
    from repro.launch.mesh import make_local_mesh

    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            f"the multidevice suite needs >= 2 devices, found {len(devs)} "
            f"{devs[0].platform} device(s)"
        )
    mesh = make_local_mesh()
    out = {"device": f"{devs[0].device_kind}x{len(devs)}", "n_dev": len(devs)}
    graphs = {}
    for name, edges in [("kronecker-11", kronecker_rmat(11, seed=0)),
                        ("watts-strogatz-20k", watts_strogatz(20000, 10, 0.1, seed=0))]:
        n = int(edges.max()) + 1
        e = jnp.asarray(edges)
        csr = preprocess(e, n_nodes=n)
        jax.block_until_ready(csr.col)
        t0 = time.perf_counter()
        csr = preprocess(e, n_nodes=n)
        jax.block_until_ready(csr.col)
        pre = time.perf_counter() - t0
        count_triangles_distributed(edges, mesh)  # warm
        t0 = time.perf_counter()
        t_dist = count_triangles_distributed(edges, mesh)
        total_dist = time.perf_counter() - t0
        count_triangles(edges)  # warm
        t0 = time.perf_counter()
        t1 = count_triangles(edges)
        total1 = time.perf_counter() - t0
        assert t_dist == t1, (name, t_dist, t1)
        graphs[name] = dict(pre_us=pre * 1e6, total_dist_us=total_dist * 1e6,
                            total1_us=total1 * 1e6,
                            amdahl_frac=pre / max(total_dist, 1e-9),
                            triangles=int(t1))
    out["graphs"] = graphs

    # --- full-workload striped vs single-device (identity-asserted) -------
    e10 = kronecker_rmat(10, seed=0)
    dist = TriangleCounter(method="distributed", mesh=mesh)
    ref = TriangleCounter(method="wedge_bsearch")
    workloads = {}
    for kind in ("per_node", "support"):
        d_fn = dist.per_node if kind == "per_node" else dist.edge_support
        r_fn = ref.per_node if kind == "per_node" else ref.edge_support
        a, b = d_fn(e10), r_fn(e10)  # warm + identity
        assert np.array_equal(a, b), kind
        assert dist.last_stats.method == "distributed"
        t0 = time.perf_counter()
        d_fn(e10)
        t_dist = time.perf_counter() - t0
        t0 = time.perf_counter()
        r_fn(e10)
        t1 = time.perf_counter() - t0
        workloads[kind] = dict(dist_us=t_dist * 1e6, wedge_us=t1 * 1e6,
                               n_stripes=dist.last_stats.n_stripes)

    e9 = kronecker_rmat(9, edge_factor=8, seed=2)
    td = k_truss_decomposition(e9, method="distributed", mesh=mesh)  # warm
    tw = k_truss_decomposition(e9, method="wedge_bsearch")
    assert td.spectrum() == tw.spectrum()
    t0 = time.perf_counter()
    td = k_truss_decomposition(e9, method="distributed", mesh=mesh)
    t_dist = time.perf_counter() - t0
    t0 = time.perf_counter()
    tw = k_truss_decomposition(e9, method="wedge_bsearch")
    t1 = time.perf_counter() - t0
    workloads["truss"] = dict(dist_us=t_dist * 1e6, wedge_us=t1 * 1e6,
                              max_k=td.max_k, rounds=td.rounds)
    out["workloads"] = workloads
    return out


def _measure_on_fake_cpus(n_devices: int = 8) -> dict:
    """Run :func:`measure` in a child that sees ``n_devices`` CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src"), _REPO, env.get("PYTHONPATH", "")]
    )
    r = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                       text=True, env=env, timeout=480)
    if r.returncode != 0:
        raise RuntimeError(
            f"multidevice child failed (exit {r.returncode}):\n{r.stderr}"
        )
    return json.loads(r.stdout.strip().splitlines()[-1])


def run():
    import jax

    if jax.default_backend() == "cpu":
        data = _measure_on_fake_cpus()
    else:
        data = measure()
    dev, n = data["device"], data["n_dev"]
    rows = []
    for name, d in data["graphs"].items():
        max_speedup = 1.0 / max(d["amdahl_frac"], 1e-9)
        rows.append((f"multidevice/{name}/{n}dev", d["total_dist_us"],
                     f"device={dev};T={d['triangles']};"
                     f"amdahl_frac={d['amdahl_frac']:.2f};"
                     f"max_speedup={min(max_speedup, n):.2f}x"))
        rows.append((f"multidevice/{name}/1dev", d["total1_us"], f"device={dev}"))
    for kind, d in data["workloads"].items():
        extra = "".join(
            f";{k}={v}" for k, v in d.items() if k not in ("dist_us", "wedge_us")
        )
        rows.append((f"multidevice/{kind}/striped-{n}dev", d["dist_us"],
                     f"device={dev}{extra}"))
        rows.append((f"multidevice/{kind}/wedge-1dev", d["wedge_us"], f"device={dev}"))
    return rows
