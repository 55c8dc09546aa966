"""Plain reference of LightGCN training (He et al., SIGIR 2020, §3),
in float32 ``jax.numpy``, importing nothing of the program.

One step: embeddings ``E0 = (users, items)``; ``L`` layers of symmetric
normalised propagation ``h_i = sum_{(u,i)} x_u / sqrt(d_u d_i)`` and
``h_u = sum_{(u,i)} x_i / sqrt(d_u d_i)`` (degrees floored at 1); the
final embedding is the mean of the ``L + 1`` layer outputs; BPR loss
``-mean log sigmoid(e_u.e_i+ - e_u.e_i-)`` plus ``l2`` times the mean
squared norm of each of the three gathered final embeddings; one Adam
update (bias-corrected, ``lr = base_lr * B / base_batch`` for the
``B`` rows of the step).

Departure from the paper, stated by the configuration: the L2 term is
taken on the propagated embeddings of the batch rows, not on the layer-0
embeddings.

Aggregation is segment sums over the COO edge list, sorted by
destination once on the host and run in blocks of edges so that the
gathered ``[block, d]`` rows fit beside the tables.  The propagation is
self-adjoint (the normalised adjacency is symmetric), so its VJP is the
same propagation applied to the cotangents.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EDGE_BLOCK = 1 << 21


def init_params(key, cfg: dict) -> dict:
    """The weights the benchmark makes from the seed: both tables
    normal with standard deviation ``1/sqrt(d)``, float32."""
    d = cfg["embed_dim"]
    ku, ki = jax.random.split(key)
    scale = 1.0 / np.sqrt(d)
    return {"user_embed": jax.random.normal(
                ku, (cfg["n_users"], d), jnp.float32) * scale,
            "item_embed": jax.random.normal(
                ki, (cfg["n_items"], d), jnp.float32) * scale}


def _blocks(dst: np.ndarray, src: np.ndarray, w: np.ndarray, block: int):
    """Edges sorted by ``dst``, padded with zero-weight edges to whole
    blocks, as ``[n_blocks, block]`` arrays."""
    order = np.argsort(dst, kind="stable")
    n_pad = -len(dst) % block
    pad = lambda a, v: np.concatenate([a[order], np.full(n_pad, v, a.dtype)])
    last = dst[order][-1] if len(dst) else 0
    cols = (pad(dst, last), pad(src, 0), pad(w, 0.0))
    return tuple(jnp.asarray(c.reshape(-1, block)) for c in cols)


class RefGraph:
    """The graph as the reference reads it: per-edge weights
    ``1/sqrt(d_u d_i)`` and both directions' destination-sorted blocks."""

    def __init__(self, user: np.ndarray, item: np.ndarray, n_users: int,
                 n_items: int, block: int = EDGE_BLOCK):
        user = np.asarray(user, np.int64)
        item = np.asarray(item, np.int64)
        du = np.maximum(np.bincount(user, minlength=n_users), 1)
        di = np.maximum(np.bincount(item, minlength=n_items), 1)
        w = (1.0 / np.sqrt(du[user].astype(np.float64)
                           * di[item])).astype(np.float32)
        block = max(1, min(block, len(user)))
        self.n_users, self.n_items = n_users, n_items
        self.to_items = _blocks(item.astype(np.int32), user.astype(np.int32),
                                w, block)
        self.to_users = _blocks(user.astype(np.int32), item.astype(np.int32),
                                w, block)


def _aggregate(x_src, blocks, n_dst):
    dst, src, w = blocks

    def body(acc, blk):
        d, s, ww = blk
        msg = x_src[s] * ww[:, None].astype(x_src.dtype)
        return acc + jax.ops.segment_sum(msg, d, num_segments=n_dst,
                                         indices_are_sorted=True), None

    acc0 = jnp.zeros((n_dst, x_src.shape[-1]), x_src.dtype)
    return jax.lax.scan(body, acc0, (dst, src, w))[0]


def _make_propagate(n_users: int, n_items: int):
    @jax.custom_vjp
    def propagate(xu, xi, to_users, to_items):
        return (_aggregate(xi, to_users, n_users),
                _aggregate(xu, to_items, n_items))

    def fwd(xu, xi, to_users, to_items):
        return propagate(xu, xi, to_users, to_items), (to_users, to_items)

    def bwd(blocks, ct):
        return (*propagate(*ct, *blocks), None, None)

    propagate.defvjp(fwd, bwd)
    return propagate


def make_loss(cfg: dict, g: RefGraph, dtype=jnp.float32, half=False):
    """BPR loss of the final embeddings, computed in ``dtype``; the
    graph's blocks are an argument, so the program stays small.
    ``half=True`` is a planted fault: the mean over the first half of
    the rows only."""
    propagate = _make_propagate(g.n_users, g.n_items)
    n_layers, l2 = cfg["n_layers"], cfg["l2"]

    def loss(params, blocks, users, pos, neg):
        xu = params["user_embed"].astype(dtype)
        xi = params["item_embed"].astype(dtype)
        acc_u, acc_i = xu, xi
        for _ in range(n_layers):
            xu, xi = propagate(xu, xi, *blocks)
            acc_u, acc_i = acc_u + xu, acc_i + xi
        eu_all, ei_all = acc_u / (n_layers + 1), acc_i / (n_layers + 1)
        if half:
            k = users.shape[0] // 2
            users, pos, neg = users[:k], pos[:k], neg[:k]
        eu, ep, en = eu_all[users], ei_all[pos], ei_all[neg]
        x = jnp.sum(eu * ep, -1) - jnp.sum(eu * en, -1)
        sq = lambda e: jnp.mean(jnp.sum(e * e, -1))
        total = -jnp.mean(jax.nn.log_sigmoid(x)) \
            + l2 * (sq(eu) + sq(ep) + sq(en))
        return total.astype(jnp.float32)

    return loss


def _leaf_norms(tree) -> dict:
    return {k: float(jnp.linalg.norm(v.astype(jnp.float32).ravel()))
            for k, v in tree.items()}


def train(cfg: dict, g: RefGraph, params0: dict, batches, *,
          dtype=jnp.float32, half=False) -> dict:
    """Run the reference over ``batches`` (host ``(users, pos, neg)``
    triples) from ``params0``: each step's loss, the first step's
    gradient norm per leaf, and the norm per leaf of the change of the
    parameters over all the steps.  Gradients and the Adam update are
    float32 whatever ``dtype`` the loss is computed in."""
    loss = make_loss(cfg, g, dtype, half)
    blocks = (g.to_users, g.to_items)
    b1, b2, eps = cfg["adam_b1"], cfg["adam_b2"], cfg["adam_eps"]
    grad = jax.jit(jax.value_and_grad(loss))

    @jax.jit
    def adam(p, m, v, gr, t, lr):
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, gr)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, gr)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(lambda p_, m_, v_:
                         p_ - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps),
                         p, m, v)
        return p, m, v

    p = params0
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for t, (u, i, n) in enumerate(batches, start=1):
            lr = cfg["base_lr"] * len(u) / cfg["base_batch"]
            val, gr = grad(p, blocks, jnp.asarray(u), jnp.asarray(i),
                           jnp.asarray(n))
            gr = jax.tree.map(lambda a: a.astype(jnp.float32), gr)
            losses.append(float(val))
            if grad_norms is None:
                grad_norms = _leaf_norms(gr)
            p, m, v = adam(p, m, v, gr, jnp.float32(t), jnp.float32(lr))
        delta = _leaf_norms(jax.tree.map(jnp.subtract, p, params0))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}
