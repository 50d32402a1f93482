"""Partition-aware distributed GNN training (the §Perf hillclimb built on
the paper's technique).

Layout produced by the Jet partitioner (dist/partition_aware.py): each
device owns a contiguous node block; edges live on their receiver's
device; senders reference either a local slot or a halo slot.  Message
passing runs under shard_map: per layer, each device exports its boundary
features once (all_gather of (H_cap, F) blocks) and aggregates locally —
replacing the naive mode's full-node all-gather + all-reduce pair.

Collective bytes per layer:
    naive       : N*F (gather) + N*F (reduce)        = 2*N*F
    partitioned : halo_frac * N * F                  (one gather)
so the partitioner's cut quality IS the communication bill.

Implemented for meshgraphnet (the hillclimb cell); the halo-exchange core
is model-agnostic.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import sharding as sh
from repro.launch.steps import Cell, _pad512, _sds
from repro.models.gnn import meshgraphnet
from repro.models.gnn.common import mlp_apply
from repro.optim import adamw


def _sizes(shape, mesh, halo_frac: float):
    n = _pad512(shape.get("n_nodes", shape.get("pad_nodes")))
    e = _pad512(shape.get("n_edges", shape.get("pad_edges")))
    d_devices = 1
    for a in mesh.axis_names:
        d_devices *= mesh.shape[a]
    n_l = n // d_devices
    e_l = e // d_devices
    h_cap = max(8, int(round(halo_frac * n_l / 8)) * 8)
    return n, e, d_devices, n_l, e_l, h_cap


def partitioned_batch_sds(shape, mesh, halo_frac: float, d_feat: int):
    n, e, d, n_l, e_l, h_cap = _sizes(shape, mesh, halo_frac)
    return {
        "node_feat": _sds((n, d_feat), jnp.float32),
        "pos": _sds((n, 3), jnp.float32),
        "target": _sds((n, 2), jnp.float32),
        # local sender index in [0, n_l + d*h_cap]  (ghost = n_l + d*h_cap)
        "senders": _sds((e,), jnp.int32),
        # local receiver index in [0, n_l]          (ghost = n_l)
        "receivers": _sds((e,), jnp.int32),
        # per-device boundary export list (local indices)
        "halo_send": _sds((d * h_cap,), jnp.int32),
        "valid_edge": _sds((e,), jnp.float32),
        "valid_node": _sds((n,), jnp.float32),
    }


def build_partitioned_batch(n, feats, pos, target, edges, parts, k,
                            n_l, e_cap_total, h_cap):
    """Host-side layout builder: partition plan -> shard_map arrays.

    edges (E, 2) directed (sender, receiver); each edge is owned by its
    receiver's device.  Returns the dict matching partitioned_batch_sds
    plus drop statistics (edges beyond per-device capacity or halo slots
    beyond h_cap are dropped and counted).
    """
    import numpy as np

    p = np.asarray(parts)[:n]
    order = np.argsort(p, kind="stable")
    slot_of = np.full(n, -1, np.int64)
    dev_of = np.empty(n, np.int64)
    counts = np.bincount(p, minlength=k)
    assert counts.max() <= n_l, (counts.max(), n_l)
    offs = np.zeros(k, np.int64)
    for v in order:
        d = p[v]
        slot_of[v] = offs[d]
        dev_of[v] = d
        offs[d] += 1
    # per-device exports: boundary vertices referenced by other devices
    src, dst = edges[:, 0], edges[:, 1]
    remote = dev_of[src] != dev_of[dst]
    exports = [dict() for _ in range(k)]  # vertex -> halo slot
    dropped_halo = 0
    for u in np.unique(src[remote]):
        d = dev_of[u]
        if len(exports[d]) < h_cap:
            exports[d][int(u)] = len(exports[d])
        else:
            dropped_halo += 1
    halo_send = np.zeros((k, h_cap), np.int64)
    for d in range(k):
        for u, s in exports[d].items():
            halo_send[d, s] = slot_of[u]
    # per-device edge lists
    e_cap = e_cap_total // k
    ghost_snd = n_l + k * h_cap
    senders = np.full((k, e_cap), ghost_snd, np.int64)
    receivers = np.full((k, e_cap), n_l, np.int64)
    valid_e = np.zeros((k, e_cap), np.float32)
    fill = np.zeros(k, np.int64)
    dropped_edges = 0
    for i in range(edges.shape[0]):
        u, v = int(src[i]), int(dst[i])
        d = int(dev_of[v])
        if fill[d] >= e_cap:
            dropped_edges += 1
            continue
        j = fill[d]
        receivers[d, j] = slot_of[v]
        if dev_of[u] == d:
            senders[d, j] = slot_of[u]
        else:
            s = exports[int(dev_of[u])].get(u)
            if s is None:
                dropped_edges += 1
                continue
            senders[d, j] = n_l + dev_of[u] * h_cap + s
        valid_e[d, j] = 1.0
        fill[d] += 1
    # node arrays in device-block layout
    F = feats.shape[1]
    nf = np.zeros((k, n_l, F), np.float32)
    ps = np.zeros((k, n_l, 3), np.float32)
    tg = np.zeros((k, n_l, target.shape[1]), np.float32)
    vn = np.zeros((k, n_l), np.float32)
    for v in range(n):
        d, s = dev_of[v], slot_of[v]
        nf[d, s] = feats[v]
        ps[d, s] = pos[v]
        tg[d, s] = target[v]
        vn[d, s] = 1.0
    import jax.numpy as jnp

    batch = {
        "node_feat": jnp.asarray(nf.reshape(k * n_l, F)),
        "pos": jnp.asarray(ps.reshape(k * n_l, 3)),
        "target": jnp.asarray(tg.reshape(k * n_l, -1)),
        "senders": jnp.asarray(senders.reshape(-1).astype(np.int32)),
        "receivers": jnp.asarray(receivers.reshape(-1).astype(np.int32)),
        "halo_send": jnp.asarray(halo_send.reshape(-1).astype(np.int32)),
        "valid_edge": jnp.asarray(valid_e.reshape(-1)),
        "valid_node": jnp.asarray(vn.reshape(-1)),
    }
    stats = {"dropped_edges": dropped_edges, "dropped_halo": dropped_halo}
    return batch, stats


def partitioned_gnn_cell(arch, shape_name, mesh, smoke=False, tuning=None):
    assert arch.id == "meshgraphnet", "partitioned mode: meshgraphnet only"
    tuning = tuning or {}
    halo_frac = tuning.get("halo_frac", 0.25)
    cfg = arch.smoke if smoke else arch.config
    shape = arch.shapes[shape_name]
    cfg = dataclasses.replace(cfg, d_in=shape["d_feat"])
    n, e, d_devices, n_l, e_l, h_cap = _sizes(shape, mesh, halo_frac)
    axes = tuple(mesh.axis_names)

    params_sds = jax.eval_shape(partial(meshgraphnet.init_params, cfg),
                                jax.random.key(0))
    p_sh = sh.gnn_param_sharding(mesh, params_sds)
    opt_sds = jax.eval_shape(adamw.init_state, params_sds)
    o_sh = sh.opt_sharding_like(p_sh, mesh)
    batch_sds = partitioned_batch_sds(shape, mesh, halo_frac, shape["d_feat"])
    b_sh = {k: NamedSharding(mesh, P(axes, *([None] * (len(v.shape) - 1))))
            for k, v in batch_sds.items()}

    def local_loss(params, b):
        """Runs per shard under shard_map; returns replicated scalar loss."""
        nf = b["node_feat"]          # (n_l, F)
        pos = b["pos"]               # (n_l, 3)
        tgt = b["target"]
        snd = b["senders"]           # (e_l,)
        rcv = b["receivers"]         # (e_l,)
        hsend = b["halo_send"]       # (h_cap,) per shard
        v_e = b["valid_edge"][:, None]
        v_n = b["valid_node"][:, None]

        def exchange(x):             # (n_l, F) -> (n_l + D*h_cap + 1, F)
            boundary = x[jnp.clip(hsend, 0, n_l - 1)]
            halo = jax.lax.all_gather(boundary, axis_name=axes)
            halo = halo.reshape(-1, x.shape[-1])
            ghost = jnp.zeros((1, x.shape[-1]), x.dtype)
            return jnp.concatenate([x, halo, ghost], 0)

        def gather_src(x_ext, idx):
            return x_ext[jnp.clip(idx, 0, n_l + d_devices * h_cap)]

        # edge geometry: receiver-local pos minus (possibly remote) sender pos
        pos_ext = exchange(pos)
        rel = (pos[jnp.clip(rcv, 0, n_l - 1)]
               - gather_src(pos_ext, snd)) * v_e
        dist = jnp.linalg.norm(rel + 1e-12, axis=-1, keepdims=True) * v_e
        efeat = jnp.concatenate([rel, dist], -1)

        h = mlp_apply(params["enc_n"], nf, act=jax.nn.relu)
        ee = mlp_apply(params["enc_e"], efeat, act=jax.nn.relu) * v_e

        @jax.checkpoint
        def block(carry, blk):
            h, ee = carry
            h_ext = exchange(h)
            hs = gather_src(h_ext, snd)
            hr = h[jnp.clip(rcv, 0, n_l - 1)]
            ee = ee + mlp_apply(blk["edge"],
                                jnp.concatenate([ee, hs, hr], -1),
                                act=jax.nn.relu) * v_e
            agg = jax.ops.segment_sum(ee, rcv, num_segments=n_l + 1)[:n_l]
            h = h + mlp_apply(blk["node"], jnp.concatenate([h, agg], -1),
                              act=jax.nn.relu)
            return (h, ee), None

        (h, ee), _ = jax.lax.scan(block, (h, ee), params["blocks"])
        pred = mlp_apply(params["dec"], h, act=jax.nn.relu)
        se = jnp.sum(((pred - tgt) ** 2) * v_n)
        cnt = jnp.sum(v_n) * cfg.d_out
        se = jax.lax.psum(se, axis_name=axes)
        cnt = jax.lax.psum(cnt, axis_name=axes)
        return se / jnp.maximum(cnt, 1.0)

    in_specs = (
        jax.tree.map(lambda _: P(), params_sds),
        {k: P(axes, *([None] * (len(v.shape) - 1)))
         for k, v in batch_sds.items()},
    )
    shard_loss = jax.shard_map(local_loss, mesh=mesh, in_specs=in_specs,
                               out_specs=P(), check_vma=False)

    opt_cfg = adamw.AdamWConfig()

    def train_step(params, opt_state, b):
        loss, grads = jax.value_and_grad(shard_loss)(params, b)
        params, opt_state, om = adamw.apply_updates(
            opt_cfg, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **om}

    fwd = meshgraphnet  # for flops estimate reuse
    from repro.launch.steps import gnn_model_flops

    return Cell(
        step_fn=train_step,
        args=(params_sds, opt_sds, batch_sds),
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
        donate=(0, 1),
        meta={
            "kind": "train",
            "param_count": cfg.param_count(),
            "active_param_count": cfg.param_count(),
            "model_flops": gnn_model_flops(arch.id, cfg, shape),
            "tokens": n,
            "mode": "partitioned",
            "halo_frac": halo_frac,
            "h_cap": h_cap,
        },
    )
