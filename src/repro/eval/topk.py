"""Streaming top-K scorer — recommendation eval/serving without the dense
score matrix.

``bpr.recall_at_k`` materializes the full ``U×I`` score matrix, which is
exactly the memory blow-up the paper's tiered design exists to avoid
(and a dead end at the "millions of users" serving scale).  This module
scores users in fixed-size microbatches against *item blocks*:

  * each (user-batch × item-block) score tile is a small dense matmul
    whose row gathers ride the same kernel dispatch as training
    (``kernels.ops.embedding_bag`` → Pallas on TPU, XLA oracle
    elsewhere), so serving traffic hits the capacity tier through the
    same DMA path the planner already costs;
  * already-seen train items are masked per block through the user-CSR
    structure — a scatter of each user's in-block item ids, never a
    dense ``U×I`` boolean mask;
  * a running per-user top-K carry merges each block via
    ``jax.lax.top_k`` over the concatenated ``[carry ‖ block]`` scores.

The sweep is *block-major*: every query batch's user rows, seen ids and
top-K carries are staged once up front (``O(n_q × (D + K + deg))``),
then each item block is gathered/uploaded exactly **once** and merged
into every batch's carry before the next block streams.  The earlier
user-major ordering re-streamed the whole catalogue per user batch —
Q× the catalogue bytes over a sweep, the exact redundant-traffic
pathology the paper's tiering analysis flags.  Peak device memory is
``O(n_q × (D + K + deg) + block × D)`` — still never the dense ``U×I``
score matrix.

When the item table is device-resident (and the sweep is unsharded) the
whole per-block pipeline instead runs as one fused gather+score+mask+
top-K kernel per user batch (``kernels.ops.fused_topk_score`` — Pallas
on TPU, a single jitted XLA loop elsewhere), keeping the same dispatch
``impl`` routing as training and bit-identical results.

Tie-breaking contract (pinned by tests/test_eval.py): results are
ordered by (score desc, item id asc) — identical to a stable dense
argsort — because ``lax.top_k`` breaks ties in favour of lower indices,
the carry precedes the block in the concatenation, block item ids are
ascending, and earlier blocks hold lower ids.  Slots with fewer than K
scoreable candidates (catalogue smaller than K, or everything masked)
return id -1 with score -inf.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.memory.executor import HostResident
from repro.pipeline.sparse import default_impl

NEG_INF = float("-inf")
DEFAULT_USER_BATCH = 256
DEFAULT_ITEM_BLOCK = 1024


def _gather_rows(table, ids, impl: str):
    """Row gather through the kernel dispatch (bag of length 1)."""
    ids = jnp.asarray(ids, jnp.int32)[:, None]
    mask = jnp.ones_like(ids, dtype=bool)
    return kops.embedding_bag(table, ids, mask, "sum", impl=impl)


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_block(ue, ie_blk, block_ids, seen, seen_mask, start,
                 carry_s, carry_i, *, k: int):
    """One streamed block: score, mask seen via scatter, top-k merge."""
    b = ue.shape[0]
    blk = ie_blk.shape[0]
    # fp32 passes: TPU's default f32 matmul rounds inputs to bf16
    scores = jnp.dot(ue, ie_blk.T,
                     precision=jax.lax.Precision.HIGHEST)   # [B, blk]
    # canonicalize -0.0 -> +0.0: lax.top_k sorts by IEEE total order
    # (-0.0 < +0.0) while comparison-based dense sorts treat them as a
    # tie — the (score desc, id asc) contract needs one behaviour
    scores = jnp.where(scores == 0.0, 0.0, scores)
    scores = jnp.where(block_ids[None, :] >= 0, scores, NEG_INF)
    # seen-item mask: scatter each user's in-block train items; the
    # extra column absorbs out-of-block ids (always in-bounds scatter)
    pos = seen - start                                      # [B, L]
    in_block = seen_mask & (pos >= 0) & (pos < blk)
    cols = jnp.where(in_block, pos, blk)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], cols.shape)
    hit = jnp.zeros((b, blk + 1), bool).at[rows, cols].set(True)[:, :blk]
    scores = jnp.where(hit, NEG_INF, scores)
    cat_s = jnp.concatenate([carry_s, scores], axis=1)
    cat_i = jnp.concatenate(
        [carry_i, jnp.broadcast_to(block_ids[None, :], scores.shape)], axis=1)
    top_s, idx = jax.lax.top_k(cat_s, k)
    return top_s, jnp.take_along_axis(cat_i, idx, axis=1)


def _padded_seen(user_ids: np.ndarray, indptr: np.ndarray, items: np.ndarray,
                 pad_to: int):
    """Ragged CSR rows -> padded [n, pad_to] ids + validity mask.
    ``pad_to`` is fixed per eval sweep so the jitted merge traces once."""
    deg = np.diff(indptr)[user_ids]
    if pad_to == 0 or len(items) == 0:
        n = len(user_ids)
        return (np.zeros((n, 0), np.int32), np.zeros((n, 0), bool))
    col = np.arange(pad_to)[None, :]
    mask = col < deg[:, None]
    idx = np.minimum(indptr[user_ids][:, None] + col, len(items) - 1)
    padded = np.where(mask, items[idx], 0).astype(np.int32)
    return padded, mask


def validate_user_ids(user_ids: np.ndarray, n_users: int) -> None:
    """Uniform out-of-range policy across placements.

    Raw numpy indexing (``HostResident.take``) wraps negative ids and
    raises on large ones, while the device gather clamps — so an
    adversarial id would silently return *different users* depending on
    where the planner happened to put the table.  Reject at the serving
    boundary instead.
    """
    if len(user_ids) == 0:
        return
    lo, hi = int(user_ids.min()), int(user_ids.max())
    if lo < 0 or hi >= n_users:
        bad = hi if hi >= n_users else lo
        raise ValueError(
            f"user_ids out of range: id {bad} not in [0, {n_users}); "
            "out-of-range ids are rejected uniformly regardless of "
            "embedding-table placement")


def streaming_topk(user_e, item_e, k: int, *, user_ids=None,
                   seen_indptr=None, seen_items=None,
                   user_batch: int = DEFAULT_USER_BATCH,
                   item_block: int = DEFAULT_ITEM_BLOCK,
                   impl: str | None = None, shard=None,
                   fused: bool | None = None):
    """Top-K items per user without materializing the U×I score matrix.

    user_e, item_e: [U, D] / [I, D] embedding tables (any tier).  A
      table demoted to a slow tier without a JAX memory kind arrives as
      a ``repro.memory.HostResident`` facade: its bytes stay in the
      host store and only each query batch's user rows / each item
      block stream to the device (row-granular gathers — bit-identical
      to the resident path, which copies the same bytes).
    user_ids: which users to score (default: all rows of user_e).
    seen_indptr/seen_items: user-CSR of already-seen (train) items to
      exclude, by global user id (``BipartiteCSR.seen_csr()`` or
      ``bpr.build_user_csr``).  None -> nothing excluded.
    shard: optional ``pipeline.shard.ShardPlan`` — user batches are
      padded to a multiple of the mesh size and their rows sharded over
      the data-parallel axes, so each device scores its slice of the
      batch against the (replicated) item blocks.  Results are
      identical to the unsharded sweep (same block schedule, same
      merges — only the batch rows are distributed).
    fused: route through the fused gather+score+top-K kernel.  None
      (default) auto-selects: fused whenever the item table is
      device-resident and the sweep is unsharded.  ``fused=True`` with a
      host-resident item table or a sharded sweep raises — the fused
      kernel needs the table addressable from device.
    Returns (scores f32[n, k], ids i32[n, k]) numpy arrays, ordered by
    (score desc, id asc); invalid slots are (-inf, -1).
    """
    impl = impl or default_impl()
    user_host = user_e if isinstance(user_e, HostResident) else None
    item_host = item_e if isinstance(item_e, HostResident) else None
    if user_host is None:
        user_e = jnp.asarray(user_e)
    if item_host is None:
        item_e = jnp.asarray(item_e)
    n_items = int(item_e.shape[0])
    n_users = int(user_e.shape[0])
    if user_ids is None:
        user_ids = np.arange(n_users, dtype=np.int32)
    user_ids = np.asarray(user_ids, np.int32)
    validate_user_ids(user_ids, n_users)
    n_q = len(user_ids)
    k = int(k)
    fused_ok = item_host is None and (shard is None or not shard.is_sharded)
    if fused and not fused_ok:
        raise ValueError(
            "fused=True needs a device-resident item table and an "
            "unsharded sweep (host-demoted tables stream block-major; "
            "sharded sweeps merge per-slice)")
    use_fused = fused_ok if fused is None else bool(fused)
    if n_q == 0 or n_items == 0:
        return (np.full((n_q, k), NEG_INF, np.float32),
                np.full((n_q, k), -1, np.int32))
    ub = int(min(user_batch, n_q))
    if shard is not None and shard.is_sharded:
        ub = math.ceil(ub / shard.n_shards) * shard.n_shards
    blk = int(min(item_block, n_items))
    n_blocks = math.ceil(n_items / blk)

    max_deg = 0
    if seen_indptr is not None:
        seen_indptr = np.asarray(seen_indptr, np.int64)
        seen_items = np.asarray(seen_items, np.int64)
        max_deg = int(np.diff(seen_indptr)[user_ids].max())
    out_s = np.full((n_q, k), NEG_INF, np.float32)
    out_i = np.full((n_q, k), -1, np.int32)

    def stage_batch(lo):
        sel = user_ids[lo:lo + ub]
        b = len(sel)
        sel_p = np.pad(sel, (0, ub - b))        # pad batch: static jit shape
        ue = jnp.asarray(user_host.take(sel_p)) if user_host is not None \
            else _gather_rows(user_e, sel_p, impl)
        if seen_indptr is not None:
            seen, smask = _padded_seen(sel_p, seen_indptr, seen_items, max_deg)
        else:
            seen = np.zeros((ub, 0), np.int32)
            smask = np.zeros((ub, 0), bool)
        return lo, b, ue, jnp.asarray(seen), jnp.asarray(smask)

    if use_fused:
        # one kernel launch per user batch — the item table never
        # leaves device memory, so there is nothing to re-stream
        for lo in range(0, n_q, ub):
            lo, b, ue, seen_d, smask_d = stage_batch(lo)
            top_s, top_i = kops.fused_topk_score(
                ue, item_e, seen_d, smask_d, k=k, n_items=n_items,
                item_block=blk, impl=impl)
            out_s[lo:lo + b] = np.asarray(top_s)[:b]
            out_i[lo:lo + b] = np.asarray(top_i)[:b]
        return out_s, out_i

    # block-major sweep: stage every user batch once, then stream each
    # item block exactly once and fold it into every batch's carry
    batches = []
    for lo in range(0, n_q, ub):
        lo, b, ue, seen_d, smask_d = stage_batch(lo)
        carry_s = jnp.full((ub, k), NEG_INF, jnp.float32)
        carry_i = jnp.full((ub, k), -1, jnp.int32)
        if shard is not None and shard.is_sharded:
            # distribute the batch rows over the dp axes; the jitted
            # merge then runs one user-slice per device (GSPMD)
            ue, seen_d, smask_d, carry_s, carry_i = shard.shard_batch(
                ue, seen_d, smask_d, carry_s, carry_i)
        batches.append([lo, b, ue, seen_d, smask_d, carry_s, carry_i])
    for b0 in range(0, n_blocks * blk, blk):
        ids_np = np.arange(b0, b0 + blk)
        valid = ids_np < n_items
        block_ids = jnp.asarray(np.where(valid, ids_np, -1).astype(np.int32))
        safe_ids = np.where(valid, ids_np, 0)
        ie_blk = jnp.asarray(item_host.block(safe_ids)) \
            if item_host is not None else _gather_rows(item_e, safe_ids, impl)
        for bt in batches:
            bt[5], bt[6] = _merge_block(
                bt[2], ie_blk, block_ids, bt[3], bt[4], jnp.int32(b0),
                bt[5], bt[6], k=k)
    for lo, b, _, _, _, carry_s, carry_i in batches:
        out_s[lo:lo + b] = np.asarray(carry_s)[:b]
        out_i[lo:lo + b] = np.asarray(carry_i)[:b]
    return out_s, out_i
