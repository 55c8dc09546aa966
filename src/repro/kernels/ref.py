"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def sddmm_ref(op: str, x: jax.Array, y: jax.Array, src: jax.Array,
              dst: jax.Array, edge_mask: jax.Array,
              coeff: jax.Array | None = None) -> jax.Array:
    a = x[src]
    if op == "copy":
        m = a if coeff is None else a * coeff[:, None]
    else:
        b = y[dst]
        if op == "mul":
            m = a * b
        elif op == "add":
            m = a + b
        elif op == "dot":
            m = jnp.sum(a * b, axis=-1)
        else:
            raise ValueError(op)
    mask = edge_mask if m.ndim == 1 else edge_mask[:, None]
    return jnp.where(mask, m, 0.0)


def spmm_csr_ref(reduce: str, values: jax.Array, indptr: jax.Array,
                 src_sorted: jax.Array, n_nodes: int,
                 gather: bool = False) -> jax.Array:
    e = src_sorted.shape[0] if gather else values.shape[0]
    # dst id per sorted edge from indptr
    dst = jnp.searchsorted(indptr, jnp.arange(e), side="right") - 1
    rows = values[src_sorted] if gather else values
    if reduce == "sum":
        return jax.ops.segment_sum(rows, dst, num_segments=n_nodes)
    if reduce == "max":
        out = jax.ops.segment_max(rows, dst, num_segments=n_nodes)
        return jnp.where(jnp.isfinite(out), out, 0.0)
    raise ValueError(reduce)


def hadamard_spmm_ref(x: jax.Array, y: jax.Array, indptr: jax.Array,
                      x_idx: jax.Array, y_idx: jax.Array, n_nodes: int,
                      scale: jax.Array | None = None,
                      slope: float | None = None) -> jax.Array:
    """Naive gather -> Hadamard -> segment-sum composition (the [E, D]
    message matrix the fused kernel avoids IS formed here — this is the
    parity ground truth, never a production route)."""
    e = x_idx.shape[0]
    dst = jnp.searchsorted(indptr, jnp.arange(e), side="right") - 1
    msgs = x.astype(jnp.float32)[x_idx] * y.astype(jnp.float32)[y_idx]
    out = jax.ops.segment_sum(msgs, dst, num_segments=n_nodes)
    if scale is not None:
        out = out * scale[:, None]
    if slope is not None:
        out = jnp.where(out >= 0, out, out * slope)
    return out


def embedding_bag_ref(table: jax.Array, ids: jax.Array, mask: jax.Array,
                      combiner: str = "sum") -> jax.Array:
    rows = table[ids]                                  # [B, L, D]
    rows = jnp.where(mask[..., None], rows, 0.0)
    out = rows.sum(axis=1)
    if combiner == "mean":
        cnt = jnp.maximum(mask.sum(axis=1), 1)
        out = out / cnt[:, None]
    return out


@jax.jit
def ann_block_scores_ref(ue: jax.Array, centroids_q: jax.Array,
                         scale: jax.Array, radius: jax.Array) -> jax.Array:
    """XLA oracle for the ANN coarse stage: per-block score upper bounds
    ``(u · ĉ_b)·scale_b + ‖u‖₂·radius_b`` over int8 block centroids.
    ue: f32[B, D]; centroids_q: i8[nb, D]; scale/radius: f32[nb] ->
    f32[B, nb].  The bound dominates every block member's exact score
    (see ``repro.serving.ann``), so pruning on it never drops a
    candidate whose bound clears the shortlist cut."""
    ue = ue.astype(jnp.float32)
    cent = centroids_q.astype(jnp.float32)
    dots = jnp.dot(ue, cent.T, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    dots = dots * scale[None, :].astype(jnp.float32)
    unorm = jnp.sqrt(jnp.sum(ue * ue, axis=1, keepdims=True))
    return dots + unorm * radius[None, :].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "item_block", "n_items"))
def fused_topk_score_ref(ue: jax.Array, table: jax.Array, seen: jax.Array,
                         seen_mask: jax.Array, *, k: int, item_block: int,
                         n_items: int):
    """XLA oracle for the fused serving kernel: one jitted sweep over
    item blocks doing score -> -0.0 canonicalization -> seen-mask ->
    running top-K merge, with the exact per-block ops (and therefore the
    exact bit patterns and tie order) of ``eval.topk``'s streamed merge.
    Returns (scores f32[B, k], ids i32[B, k]); short slots are
    (-inf, -1)."""
    neg_inf = float("-inf")
    b = ue.shape[0]
    blk = int(min(item_block, max(n_items, 1)))
    n_blocks = -(-n_items // blk)
    tpad = n_blocks * blk - table.shape[0]
    table = jnp.pad(table, ((0, tpad), (0, 0))) if tpad else table
    table = table.astype(jnp.float32)
    ue = ue.astype(jnp.float32)
    seen = jnp.asarray(seen, jnp.int32)
    seen_mask = jnp.asarray(seen_mask, bool)
    rows_b = jnp.arange(b)[:, None]

    def body(j, carry):
        carry_s, carry_i = carry
        start = j * blk
        ie_blk = jax.lax.dynamic_slice_in_dim(table, start, blk, axis=0)
        scores = jnp.dot(ue, ie_blk.T, precision=jax.lax.Precision.HIGHEST)
        scores = jnp.where(scores == 0.0, 0.0, scores)
        ids = start + jax.lax.broadcasted_iota(jnp.int32, (b, blk), 1)
        scores = jnp.where(ids < n_items, scores, neg_inf)
        pos = seen - start
        in_block = seen_mask & (pos >= 0) & (pos < blk)
        cols = jnp.where(in_block, pos, blk)           # overflow column
        hit = jnp.zeros((b, blk + 1), bool).at[rows_b, cols].set(True)[:, :blk]
        scores = jnp.where(hit, neg_inf, scores)
        cat_s = jnp.concatenate([carry_s, scores], axis=1)
        cat_i = jnp.concatenate([carry_i, ids], axis=1)
        top_s, idx = jax.lax.top_k(cat_s, k)
        return top_s, jnp.take_along_axis(cat_i, idx, axis=1)

    init = (jnp.full((b, k), neg_inf, jnp.float32),
            jnp.full((b, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, n_blocks, body, init)
