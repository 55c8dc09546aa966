"""Model registry for the unified training pipeline.

One contract for every GNNRecSys architecture:

    init(key, n_users, n_items, embed_dim, n_layers) -> params
    forward(params, g: BipartiteCSR, n_layers) -> (user_emb, item_emb)

All three forwards route aggregation through the kernel-dispatched CSR
ops in ``pipeline.sparse`` (Pallas SpMM on TPU, XLA oracle elsewhere)
and are numerically equivalent to the seed COO implementations in
``repro.core`` — tests/test_pipeline.py pins that equivalence.

  lightgcn — He et al. SIGIR'20; the paper's fastest model.
  ngcf     — Wang et al. SIGIR'19, Eq. (7) and (9): per layer
             E' = LeakyReLU((L + I) E W1 + ((L E) * E) W2), output the
             concat of every layer.  The Hadamard term factors, so a
             layer is one ``sym_propagate`` and a node-level transform
             (``hadamard`` 'fused'/'composed' aggregate it again).
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

from repro import obs
from repro.core import lightgcn as _lightgcn
from repro.core import ngcf as _ngcf
from repro.pipeline.sparse import BipartiteCSR


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    init: Callable          # (key, n_users, n_items, embed_dim, n_layers)
    forward: Callable       # (params, g, n_layers) -> (user_emb, item_emb)
    materializes_messages: bool   # has a route with an [E, D] edge matrix
    concat_layers: bool = False   # output concatenates all layer embeddings

    def out_dim(self, embed_dim: int, n_layers: int) -> int:
        """Final embedding width (drives the planner's per-sample cost)."""
        return embed_dim * (n_layers + 1) if self.concat_layers else embed_dim

    def messages_materialized(self, g: "BipartiteCSR | None" = None) -> bool:
        """Whether THIS run's forward forms the per-layer [E, embed_dim]
        message matrix: only NGCF's 'composed' Hadamard route does, so
        the planner profiles that stream for it alone."""
        return self.materializes_messages \
            and getattr(g, "hadamard_route", None) == "composed"


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


def _dot(x, w):
    """``x @ w`` in float32 on every backend (a TPU's default is one
    bfloat16 pass)."""
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _ngcf_hadamard(g: BipartiteCSR, xu, xi):
    """The Hadamard term ``sum_e p_ui x_src * x_dst`` of both directions
    through an aggregation of its own ('fused' or 'composed' route), on
    rows pre-scaled by ``1/sqrt(degree)``."""
    su, si = xu * g.rsqrt_du[:, None], xi * g.rsqrt_di[:, None]
    if g.hadamard_route == "fused":
        # gather-Hadamard-aggregate with a rematerializing VJP
        return g.hadamard_agg_user(si, su), g.hadamard_agg_item(su, si)
    # O3: one edge product per layer, reused for both directions
    mul_ui = su[g.ui_src] * si[g.ui_dst]                 # [E, D], ui order
    return (g.edge_agg_user(mul_ui[g.perm_ui_to_iu]),
            g.edge_agg_item(mul_ui))


def _ngcf_transform(x, side, had, w1, w2):
    """Eq. (7) at node level: ``side = (L E)``, ``had = (L E) * E``
    (None: formed here)."""
    with obs.transform_scope():
        if had is None:
            had = side * x
        return jax.nn.leaky_relu(_dot(side + x, w1) + _dot(had, w2), 0.2)


def _ngcf_forward(params, g: BipartiteCSR, n_layers: int):
    xu, xi = params["user_embed"], params["item_embed"]
    outs_u, outs_i = [xu], [xi]
    route = g.hadamard_route
    obs.count("ngcf_route", route)
    for w1, w2 in zip(params["w1"], params["w2"]):
        side_u, side_i = g.sym_propagate(xu, xi)
        had_u = had_i = None
        if route != "factored":
            had_u, had_i = _ngcf_hadamard(g, xu, xi)
        xu, xi = (_ngcf_transform(xu, side_u, had_u, w1, w2),
                  _ngcf_transform(xi, side_i, had_i, w1, w2))
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
        with obs.transform_scope():
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
                      materializes_messages=True, concat_layers=True),
    "gcn": ModelSpec("gcn", _gcn_init, _gcn_forward,
                     materializes_messages=False),
}


def get_model(name: str) -> ModelSpec:
    if name not in MODELS:
        raise KeyError(f"unknown pipeline model {name!r}; "
                       f"known: {sorted(MODELS)}")
    return MODELS[name]
