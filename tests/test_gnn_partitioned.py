"""Partition-aware GNN distribution: numerical equivalence with the dense
reference under a real multi-device shard_map (8 host devices, subprocess
so the 512-device dry-run env stays isolated)."""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core.partition import PartitionConfig, partition
from repro.core.graph import build_csr_host, graph_to_host
from repro.data import graphs as gen
from repro.launch.gnn_partitioned import (
    build_partitioned_batch, partitioned_gnn_cell)
from repro.configs import get_arch
from repro.models.gnn import meshgraphnet
from repro.models.gnn.common import GraphBatch

K = 8
g = gen.grid2d(16, 16)  # 256 nodes
n = int(g.n)
rng = np.random.default_rng(0)
feats = rng.standard_normal((n, 4)).astype(np.float32)
pos = rng.standard_normal((n, 3)).astype(np.float32)
target = rng.standard_normal((n, 2)).astype(np.float32)
m = int(g.m)
edges = np.stack([np.asarray(g.esrc)[:m], np.asarray(g.adjncy)[:m]], 1)

res = partition(g, PartitionConfig(k=K, lam=0.10))
assert res.balanced

cfg = meshgraphnet.MGNConfig(n_layers=3, d_hidden=16, d_in=4)
params = meshgraphnet.init_params(cfg, jax.random.key(0))

# dense reference loss
ref_batch = {
    "graph": GraphBatch(
        node_feat=jnp.asarray(feats), senders=jnp.asarray(edges[:,0].astype(np.int32)),
        receivers=jnp.asarray(edges[:,1].astype(np.int32)), edge_feat=None,
        pos=jnp.asarray(pos), graph_id=jnp.zeros((n,), jnp.int32), n_graphs=1),
    "target": jnp.asarray(target),
}
ref_loss = float(meshgraphnet.loss_fn(cfg, params, ref_batch)[0])

# partitioned loss under shard_map on an 8-device mesh
mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
n_l = 64  # 256/8 = 32; pad blocks to 64 for slack
h_cap = 64
e_cap_total = 2048
batch, stats = build_partitioned_batch(
    n, feats, pos, target, edges, res.parts, K, n_l, e_cap_total, h_cap)
assert stats["dropped_edges"] == 0, stats
assert stats["dropped_halo"] == 0, stats

arch = get_arch("meshgraphnet")
shape = {"kind": "train", "n_nodes": K*n_l, "n_edges": e_cap_total,
         "d_feat": 4, "n_graphs": 1}
arch2 = dataclasses.replace(
    arch, shapes=dict(arch.shapes, test_shape=shape),
    config=cfg, smoke=cfg)
cell = partitioned_gnn_cell(arch2, "test_shape", mesh,
                            tuning={"halo_frac": 1.0})
# align h_cap: our builder used h_cap=64 = 1.0 * n_l -> matches tuning
from repro.optim import adamw
opt = adamw.init_state(params)
step = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
               out_shardings=cell.out_shardings, donate_argnums=cell.donate)
p2, o2, metrics = step(params, opt, batch)
part_loss = float(metrics["loss"])
print("REF", ref_loss, "PART", part_loss)
assert abs(part_loss - ref_loss) / max(abs(ref_loss), 1e-9) < 1e-4, (
    ref_loss, part_loss)
print("OK")
"""


def test_partitioned_equivalence_8dev():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo", timeout=600)
    assert "OK" in r.stdout, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
