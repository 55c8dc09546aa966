"""Model registry for the unified training pipeline.

One contract for every GNNRecSys architecture:

    init(key, n_users, n_items, embed_dim, n_layers) -> params
    forward(params, g: BipartiteCSR, n_layers) -> (user_emb, item_emb)

All three forwards route aggregation through the kernel-dispatched CSR
ops in ``pipeline.sparse`` (Pallas SpMM on TPU, XLA oracle elsewhere)
and are numerically equivalent to the seed COO implementations in
``repro.core`` — tests/test_pipeline.py pins that equivalence.

  lightgcn — He et al. SIGIR'20; the paper's fastest model.
  ngcf     — Wang et al. SIGIR'19 with the §4 O1-O3 dataflow rewrites
             (single Hadamard SDDMM per layer, reused for both
             directions via the edge permutation).
  gcn      — Kipf-Welling convolution applied to the user-item graph
             (sym-normalized propagate + per-layer weight + ReLU),
             BPR-trained like the others; paper §9 notes GCN's scalar
             message fuses into a single SpMM, which is exactly the
             ``sym_propagate`` path.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import lightgcn as _lightgcn
from repro.core import ngcf as _ngcf
from repro.pipeline.sparse import BipartiteCSR


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    init: Callable          # (key, n_users, n_items, embed_dim, n_layers)
    forward: Callable       # (params, g, n_layers) -> (user_emb, item_emb)
    materializes_messages: bool   # [E, embed_dim] edge matrix per layer
    concat_layers: bool = False   # output concatenates all layer embeddings
    # ring SpMMs the forward and its VJP apply under the ring dispatch
    ring_ops: tuple[str, ...] = ("sym", "sym_T")

    def out_dim(self, embed_dim: int, n_layers: int) -> int:
        """Final embedding width (drives the planner's per-sample cost)."""
        return embed_dim * (n_layers + 1) if self.concat_layers else embed_dim

    def messages_materialized(self, g: "BipartiteCSR | None" = None) -> bool:
        """Whether THIS run's forward actually forms the per-layer
        [E, embed_dim] message matrix: the graph's fused Hadamard route
        keeps it out of memory entirely, so the planner must not
        profile the stream it no longer carries."""
        return self.materializes_messages \
            and not getattr(g, "fused_hadamard", False)


# ---------------------------------------------------------------- lightgcn
def _lightgcn_init(key, n_users, n_items, embed_dim, n_layers):
    return _lightgcn.init_params(key, n_users, n_items, embed_dim)


def _lightgcn_forward(params, g: BipartiteCSR, n_layers: int):
    xu, xi = params["user_embed"], params["item_embed"]
    acc_u, acc_i = xu, xi
    for _ in range(n_layers):
        xu, xi = g.sym_propagate(xu, xi)
        acc_u = acc_u + xu
        acc_i = acc_i + xi
    denom = n_layers + 1
    return acc_u / denom, acc_i / denom


# ---------------------------------------------------------------- ngcf
def _ngcf_init(key, n_users, n_items, embed_dim, n_layers):
    return _ngcf.init_params(key, n_users, n_items, embed_dim, n_layers)


def _ngcf_forward(params, g: BipartiteCSR, n_layers: int):
    xu, xi = params["user_embed"], params["item_embed"]
    outs_u, outs_i = [xu], [xi]
    fused = getattr(g, "fused_hadamard", False)
    for w1, w2 in zip(params["w1"], params["w2"]):
        if fused:
            # fused gather-Hadamard-aggregate (rematerializing VJP):
            # the [E, D] message matrix never exists in memory
            agg_mul_item = g.hadamard_agg_item(xu, xi)
            agg_mul_user = g.hadamard_agg_user(xi, xu)
        else:
            # O3: one Hadamard SDDMM per layer, reused for both directions
            mul_ui = xu[g.ui_src] * xi[g.ui_dst]         # [E, D], ui order
            agg_mul_item = g.edge_agg_item(mul_ui)
            agg_mul_user = g.edge_agg_user(mul_ui[g.perm_ui_to_iu])
        # O1: aggregate raw src features first, matmul at node level
        h_item = agg_mul_item @ w1 + g.agg_u2i(xu) @ w2
        h_user = agg_mul_user @ w1 + g.agg_i2u(xi) @ w2
        xu = jax.nn.leaky_relu(h_user, 0.2)
        xi = jax.nn.leaky_relu(h_item, 0.2)
        outs_u.append(xu)
        outs_i.append(xi)
    return jnp.concatenate(outs_u, -1), jnp.concatenate(outs_i, -1)


# ---------------------------------------------------------------- gcn
def _gcn_init(key, n_users, n_items, embed_dim, n_layers):
    keys = jax.random.split(key, 2 + n_layers)
    scale = 1.0 / jnp.sqrt(embed_dim)
    params = {
        "user_embed": jax.random.normal(
            keys[0], (n_users, embed_dim), jnp.float32) * scale,
        "item_embed": jax.random.normal(
            keys[1], (n_items, embed_dim), jnp.float32) * scale,
        "layers": [],
    }
    for l in range(n_layers):
        w = jax.random.normal(keys[2 + l], (embed_dim, embed_dim),
                              jnp.float32) * jnp.sqrt(2.0 / embed_dim)
        params["layers"].append({"w": w, "b": jnp.zeros((embed_dim,), jnp.float32)})
    return params


def _gcn_forward(params, g: BipartiteCSR, n_layers: int):
    xu, xi = params["user_embed"], params["item_embed"]
    for l, lyr in enumerate(params["layers"]):
        hu, hi = g.sym_propagate(xu, xi)
        xu = hu @ lyr["w"] + lyr["b"]
        xi = hi @ lyr["w"] + lyr["b"]
        if l + 1 < len(params["layers"]):
            xu = jax.nn.relu(xu)
            xi = jax.nn.relu(xi)
    return xu, xi


MODELS = {
    "lightgcn": ModelSpec("lightgcn", _lightgcn_init, _lightgcn_forward,
                          materializes_messages=False),
    "ngcf": ModelSpec("ngcf", _ngcf_init, _ngcf_forward,
                      materializes_messages=True, concat_layers=True,
                      ring_ops=("ui", "iu", "ui_T", "iu_T")),
    "gcn": ModelSpec("gcn", _gcn_init, _gcn_forward,
                     materializes_messages=False),
}


def get_model(name: str) -> ModelSpec:
    if name not in MODELS:
        raise KeyError(f"unknown pipeline model {name!r}; "
                       f"known: {sorted(MODELS)}")
    return MODELS[name]
