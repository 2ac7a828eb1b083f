"""Train a 2-layer GCN (the gcn-cora architecture) on a synthetic
Cora-like graph with the bulk message-passing substrate, plus one step of
GatedGCN to show the arch switch.

  PYTHONPATH=src python examples/train_gnn.py
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.base import shape
from repro.configs.registry import ARCHS
from repro.data.graphs import build_graph
from repro.models.gnn import gnn_forward, gnn_loss, init_gnn_params
from repro.optim.adamw import AdamWConfig, adamw_update, init_adamw

use_compile_cache()

cfg = dataclasses.replace(ARCHS["gcn-cora"].config, d_in=64, d_out=7)
spec = shape("demo", "gnn_full", n_nodes=512, n_edges=4096, d_feat=64)
g = build_graph(cfg, spec)
rng = np.random.default_rng(0)
labels = jnp.asarray(rng.integers(0, 7, 512).astype(np.int32))
mask = jnp.ones((512,), jnp.float32)
batch = dict(graph=g, labels=labels, mask=mask)

params = init_gnn_params(cfg, jax.random.PRNGKey(0))
opt_cfg = AdamWConfig(lr=1e-2, total_steps=60)
opt = init_adamw(params)


@jax.jit
def step(params, opt, batch):
    loss, grads = jax.value_and_grad(
        lambda p: gnn_loss(cfg, p, batch))(params)
    params, opt, _ = adamw_update(opt_cfg, grads, opt, params)
    return params, opt, loss


losses = []
for s in range(60):
    params, opt, loss = step(params, opt, batch)
    losses.append(float(loss))
    if s % 15 == 0:
        print(f"gcn step {s}: loss {float(loss):.4f}")
print(f"GCN loss {losses[0]:.3f} -> {losses[-1]:.3f}")
assert losses[-1] < losses[0]

# arch switch: one GatedGCN step on the same graph
cfg2 = dataclasses.replace(ARCHS["gatedgcn"].smoke(), d_in=64, d_out=7)
p2 = init_gnn_params(cfg2, jax.random.PRNGKey(1))
out = jax.jit(lambda p, g: gnn_forward(cfg2, p, g))(p2, g)
print("gatedgcn forward ok:", out.shape)
