"""Shared builders for the four GNN architectures × four graph shapes.

Shapes (assigned):

* ``full_graph_sm``  — Cora-size full-batch training (2 708 / 10 556 / 1433),
* ``minibatch_lg``   — Reddit-size sampled training (232 965 nodes,
  114.6M directed edges, 1 024 seed nodes, fanout 15-10) with the *real*
  fanout sampler from :mod:`repro.graphs.sampling` running inside the step,
* ``ogb_products``   — 2.45M-node / 61.9M-edge full-batch,
* ``molecule``       — 128 × (30-node, 64-edge) batched small graphs,
  regression readout.

Distribution (paper-derived): node features replicated, **edge lists
partitioned** across the whole mesh, partial aggregations reduced — the
multi-GPU scheme of the paper transplanted onto message passing.  For the
minibatch shape the sampler state (seeds) shards over the batch axes.

Non-SAGE archs have no native layered-block formulation, so the sampled
frontiers are linearized into an explicit block *graph* (child→parent
edges) and run through the arch's ordinary edge-list ``apply`` — one code
path serves all four archs on ``minibatch_lg``.  GraphSAGE uses its
faithful ``apply_blocks``.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.graphs.sampling import sample_blocks
from repro.optim import adamw, apply_updates, constant

from .base import DryRunSpec, dp_axes, named, pad_to, rep, sds

__all__ = ["GNN_SHAPES", "build_gnn_dryrun", "block_graph_from_frontiers"]

GNN_SHAPES = {
    "full_graph_sm": dict(
        kind="full", n_nodes=2708, n_edges=10556, d_feat=1433, n_classes=7
    ),
    "minibatch_lg": dict(
        kind="minibatch",
        n_nodes=232965,
        n_edges=114615892,
        batch_nodes=1024,
        fanout=(15, 10),
        d_feat=602,
        n_classes=41,
    ),
    "ogb_products": dict(
        kind="full", n_nodes=2449029, n_edges=61859140, d_feat=100, n_classes=47
    ),
    "molecule": dict(kind="batched", n_nodes=30, n_edges=64, batch=128, d_feat=16),
}


def block_graph_from_frontiers(frontiers, fanouts):
    """Linearize sampled frontiers into one block graph.

    Returns (block_node_ids, edge_src, edge_dst): positions index into the
    concatenated frontier list; edges run child→parent and parent→child.
    """
    offsets = [0]
    for f in frontiers:
        offsets.append(offsets[-1] + f.shape[0])
    nodes = jnp.concatenate(frontiers)
    srcs, dsts = [], []
    for lvl, fanout in enumerate(fanouts):
        n_parent = frontiers[lvl].shape[0]
        parent_pos = offsets[lvl] + jnp.arange(n_parent, dtype=jnp.int32)
        child_pos = offsets[lvl + 1] + jnp.arange(n_parent * fanout, dtype=jnp.int32)
        parent_rep = jnp.repeat(parent_pos, fanout)
        srcs += [child_pos, parent_rep]
        dsts += [parent_rep, child_pos]
    return nodes, jnp.concatenate(srcs), jnp.concatenate(dsts)


def _synth_positions(node_ids: jax.Array) -> jax.Array:
    """Deterministic pseudo-positions for geometric models on non-molecular
    graphs (DESIGN.md §4): a cheap integer hash → 3 floats in [−1, 1]."""
    x = node_ids.astype(jnp.uint32)
    out = []
    for c in (2654435761, 2246822519, 3266489917):
        h = (x * jnp.uint32(c)) ^ (x >> jnp.uint32(13))
        out.append((h % jnp.uint32(65536)).astype(jnp.float32) / 32768.0 - 1.0)
    return jnp.stack(out, axis=1)


def _ce_loss(logits, labels, n_valid=None):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    valid = (labels >= 0).astype(jnp.float32)  # −1 = padded node
    return -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def _estimate_flops(arch_flops_per_edge, arch_flops_per_node, n_nodes, n_edges, train=True):
    f = arch_flops_per_edge * n_edges + arch_flops_per_node * n_nodes
    return f * (3.0 if train else 1.0)


def build_gnn_dryrun(
    arch_id: str,
    model_mod,            # repro.models.gnn.<arch> module
    make_cfg: Callable,   # (d_in, d_out) -> config dataclass
    shape_name: str,
    mesh,
    flops_per_edge: float,
    flops_per_node: float,
    variant: str = "baseline",
):
    """§Perf variants (full-graph shapes):

    * ``variant="opt"`` — keep the paper's replicated-nodes /
      partitioned-edges scheme but run aggregation in **bf16**: the
      dominant collective is the per-layer psum of (N, d) partial
      aggregates, whose bytes halve with the dtype.
    * ``variant="nodeshard"`` — node-sharded features (tried first and
      REFUTED: GSPMD cannot halo-exchange an unstructured gather, so it
      all-gathers the sharded features *and* reshards — ~2× worse;
      kept selectable for the record).
    """
    shape = GNN_SHAPES[shape_name]
    dp = dp_axes(mesh)
    dpP = dp if len(dp) > 1 else dp[0]
    all_axes = tuple(mesh.axis_names)
    opt_init, opt_update = adamw(constant(1e-3), weight_decay=0.0)
    node_sharded = variant == "nodeshard" and shape["kind"] == "full"

    if shape["kind"] == "full":
        n, e, f, c = shape["n_nodes"], shape["n_edges"], shape["d_feat"], shape["n_classes"]
        e = pad_to(e)  # −1-padded tail; every consumer masks
        if node_sharded:
            n = pad_to(n)  # padded nodes carry label −1 (masked in the loss)
        cfg = make_cfg(f, c)
        shardmap_psum = variant == "opt2" and hasattr(make_cfg(1, 1), "psum_axes")
        if variant in ("opt", "opt2"):
            import dataclasses

            cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
            if hasattr(cfg, "smart_order"):
                cfg = dataclasses.replace(cfg, smart_order=True)
        if shardmap_psum:
            import dataclasses

            # explicit shard_map edge-parallelism: per-layer psums emitted
            # in bf16 (GSPMD's implicit all-reduce hoists the upcast)
            cfg = dataclasses.replace(cfg, psum_axes=all_axes)
        params_sds = jax.eval_shape(lambda k: model_mod.init_params(k, cfg), jax.random.PRNGKey(0))

        if shardmap_psum:
            from jax import shard_map

            def shard_loss(p, feat, pos, src, dst, labels):
                out = model_mod.apply(
                    p, cfg, feat, pos, src.reshape(-1), dst.reshape(-1)
                )
                return _ce_loss(out, labels)

            sharded_loss = shard_map(
                shard_loss,
                mesh=mesh,
                in_specs=(P(), P(), P(), P(all_axes), P(all_axes), P()),
                out_specs=P(),
                check_vma=False,
            )

            def step(params, opt_state, feat, pos, edge_src, edge_dst, labels):
                l, grads = jax.value_and_grad(
                    lambda p: sharded_loss(p, feat, pos, edge_src, edge_dst, labels)
                )(params)
                updates, opt_state, _ = opt_update(grads, opt_state, params)
                return apply_updates(params, updates), opt_state, {"loss": l}
        else:
            def step(params, opt_state, feat, pos, edge_src, edge_dst, labels):
                def loss(p):
                    out = model_mod.apply(p, cfg, feat, pos, edge_src, edge_dst)
                    if node_sharded:
                        out = jax.lax.with_sharding_constraint(
                            out, NamedSharding(mesh, P(all_axes, None))
                        )
                    return _ce_loss(out, labels)

                l, grads = jax.value_and_grad(loss)(params)
                updates, opt_state, _ = opt_update(grads, opt_state, params)
                return apply_updates(params, updates), opt_state, {"loss": l}

        opt_sds = jax.eval_shape(opt_init, params_sds)
        args = (
            params_sds,
            opt_sds,
            sds((n, f)),
            sds((n, 3)),
            sds((e,), jnp.int32),
            sds((e,), jnp.int32),
            sds((n,), jnp.int32),
        )
        node_sh = named(mesh, all_axes, None) if node_sharded else rep(mesh)
        label_sh = named(mesh, all_axes) if node_sharded else rep(mesh)
        in_sh = (
            rep(mesh),
            rep(mesh),
            node_sh,
            node_sh,
            named(mesh, all_axes),
            named(mesh, all_axes),
            label_sh,
        )
        return DryRunSpec(
            step_fn=step,
            args=args,
            in_shardings=in_sh,
            donate_argnums=(0, 1),
            description=f"{arch_id} full-graph N={n} E={e} ({variant})",
            model_flops=_estimate_flops(flops_per_edge, flops_per_node, n, e),
            n_params=0,
            tokens_per_step=n,
        )

    if shape["kind"] == "minibatch":
        n, e, f, c = shape["n_nodes"], shape["n_edges"], shape["d_feat"], shape["n_classes"]
        b, fanout = shape["batch_nodes"], shape["fanout"]
        cfg = make_cfg(f, c)
        params_sds = jax.eval_shape(lambda k: model_mod.init_params(k, cfg), jax.random.PRNGKey(0))
        use_blocks = hasattr(model_mod, "apply_blocks")

        def step(params, opt_state, key, row_offsets, col, feat, seeds, labels):
            blocks = sample_blocks(key, row_offsets, col, seeds, fanout)

            def loss(p):
                if use_blocks:
                    feats = [jnp.take(feat, fr, axis=0) for fr in blocks.frontiers]
                    out = model_mod.apply_blocks(p, cfg, feats, fanout)
                else:
                    nodes, esrc, edst = block_graph_from_frontiers(blocks.frontiers, fanout)
                    nf = jnp.take(feat, nodes, axis=0)
                    pos = _synth_positions(nodes)
                    out = model_mod.apply(p, cfg, nf, pos, esrc, edst)[: seeds.shape[0]]
                return _ce_loss(out, labels)

            l, grads = jax.value_and_grad(loss)(params)
            updates, opt_state, _ = opt_update(grads, opt_state, params)
            return apply_updates(params, updates), opt_state, {"loss": l}

        opt_sds = jax.eval_shape(opt_init, params_sds)
        args = (
            params_sds,
            opt_sds,
            sds((2,), jnp.uint32),
            sds((n + 1,), jnp.int32),
            sds((e,), jnp.int32),
            sds((n, f)),
            sds((b,), jnp.int32),
            sds((b,), jnp.int32),
        )
        in_sh = (
            rep(mesh),
            rep(mesh),
            rep(mesh),
            rep(mesh),
            rep(mesh),
            rep(mesh),
            named(mesh, dpP),
            named(mesh, dpP),
        )
        sampled_edges = b * (fanout[0] + fanout[0] * fanout[1]) * 2
        sampled_nodes = b * (1 + fanout[0] + fanout[0] * fanout[1])
        return DryRunSpec(
            step_fn=step,
            args=args,
            in_shardings=in_sh,
            donate_argnums=(0, 1),
            description=f"{arch_id} minibatch B={b} fanout={fanout}",
            model_flops=_estimate_flops(flops_per_edge, flops_per_node, sampled_nodes, sampled_edges),
            n_params=0,
            tokens_per_step=b,
        )

    # batched small graphs (molecule): regression readout
    nb, ne, batch, f = shape["n_nodes"], shape["n_edges"], shape["batch"], shape["d_feat"]
    cfg = make_cfg(f, 1)
    params_sds = jax.eval_shape(lambda k: model_mod.init_params(k, cfg), jax.random.PRNGKey(0))

    def step(params, opt_state, node_feat, positions, edge_src, edge_dst, labels):
        def loss(p):
            bsz = node_feat.shape[0]
            flat_feat = node_feat.reshape(bsz * nb, -1)
            flat_pos = positions.reshape(bsz * nb, 3)
            off = (jnp.arange(bsz, dtype=jnp.int32) * nb)[:, None]
            fsrc = jnp.where(edge_src >= 0, edge_src + off, -1).reshape(-1)
            fdst = jnp.where(edge_dst >= 0, edge_dst + off, -1).reshape(-1)
            out = model_mod.apply(p, cfg, flat_feat, flat_pos, fsrc, fdst)  # (B*nb, 1)
            graph_ids = jnp.repeat(jnp.arange(bsz, dtype=jnp.int32), nb)
            pred = jax.ops.segment_sum(out[:, 0], graph_ids, num_segments=bsz)
            return jnp.mean((pred - labels) ** 2)

        l, grads = jax.value_and_grad(loss)(params)
        updates, opt_state, _ = opt_update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, {"loss": l}

    opt_sds = jax.eval_shape(opt_init, params_sds)
    args = (
        params_sds,
        opt_sds,
        sds((batch, nb, f)),
        sds((batch, nb, 3)),
        sds((batch, ne), jnp.int32),
        sds((batch, ne), jnp.int32),
        sds((batch,)),
    )
    in_sh = (
        rep(mesh),
        rep(mesh),
        named(mesh, dpP, None, None),
        named(mesh, dpP, None, None),
        named(mesh, dpP, None),
        named(mesh, dpP, None),
        named(mesh, dpP),
    )
    return DryRunSpec(
        step_fn=step,
        args=args,
        in_shardings=in_sh,
        donate_argnums=(0, 1),
        description=f"{arch_id} molecule batch={batch}",
        model_flops=_estimate_flops(flops_per_edge, flops_per_node, batch * nb, batch * ne),
        n_params=0,
        tokens_per_step=batch,
    )
