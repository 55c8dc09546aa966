"""Pallas TPU fused serving kernel — gather + score + seen-mask + top-K.

The serving hot loop used to run as four separate XLA ops per item
block (row gather -> dense matmul -> seen-mask scatter -> ``lax.top_k``
merge), each a separate dispatch with its own HBM round-trip for the
score tile.  This kernel is the paper's §4 dataflow rewrite applied to
serving: the item table stays in HBM (on a real deployment, the
capacity tier) and each program

  * DMAs one item *block* of rows into VMEM (the row gather — only the
    block's bytes ever leave HBM),
  * scores its user tile against the block on the MXU,
  * masks already-seen items in place (no dense U×I boolean mask),
  * folds the block into a running per-user top-K carry,

so the score tile never leaves VMEM and the only HBM writes are the
final ``[B, K]`` results.  The grid tiles the *user batch* (tiles are
independent — no cross-program carry); the block loop runs inside each
program with the carry as a ``fori_loop`` value.

Layout: users ride the 128-wide lane axis and items the sublane axis
(the score tile is ``[blk, tile]``), so each user's seen ids arrive as
one lane-vector per seen slot and every reduction runs over sublanes.
Seen ids stay in HBM (``[L, B]``, -1 = padding) and stream through VMEM
in ``SEEN_CHUNK``-row slabs, so VMEM use does not grow with the
heaviest user's history.

Top-K merge: ``k`` rounds of select-max over ``[carry ‖ block]``; each
round takes the largest score and, among equal scores, the lowest item
id, then retires that (unique) id.  Tie-breaking contract (identical to
``eval/topk.py``'s streamed ``lax.top_k`` merge, pinned by
tests/test_kernel_parity.py): results are ordered by (score desc, item
id asc).  Scores equal to zero are canonicalized to +0.0 first —
``lax.top_k`` sorts by IEEE total order (-0.0 < +0.0) while
comparison-based dense sorts treat them as a tie.  Slots with fewer
than K scoreable candidates return id -1 with score -inf.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
DEFAULT_USER_TILE = 128
SEEN_CHUNK = 256
_NO_ID = jnp.iinfo(jnp.int32).max


def _merge(carry_s, carry_i, scores, ids, k: int):
    """New ``[k, tile]`` carry from the old one and a ``[blk, tile]``
    block, by k rounds of (max score, then lowest id)."""
    slot = jax.lax.broadcasted_iota(jnp.int32, carry_s.shape, 0)

    def round_(r, st):
        cs, bs, out_s, out_i = st
        m = jnp.maximum(jnp.max(cs, axis=0, keepdims=True),
                        jnp.max(bs, axis=0, keepdims=True))      # [1, tile]
        sel = jnp.minimum(
            jnp.min(jnp.where(cs == m, carry_i, _NO_ID), axis=0,
                    keepdims=True),
            jnp.min(jnp.where(bs == m, ids, _NO_ID), axis=0, keepdims=True))
        # a -inf maximum means nothing scoreable is left: (-inf, -1)
        sel = jnp.where(m > NEG_INF, sel, -1)
        out_s = jnp.where(slot == r, m, out_s)
        out_i = jnp.where(slot == r, sel, out_i)
        # finite (score, id) pairs are unique: retire the chosen id
        cs = jnp.where(carry_i == sel, NEG_INF, cs)
        bs = jnp.where(ids == sel, NEG_INF, bs)
        return cs, bs, out_s, out_i

    _, _, out_s, out_i = jax.lax.fori_loop(
        0, k, round_, (carry_s, scores, carry_s, carry_i))
    return out_s, out_i


def _kernel(table_hbm, seen_hbm, ue_ref, out_s_ref, out_i_ref, blk_buf,
            seen_buf, sem, *, blk: int, n_blocks: int, n_items: int, k: int,
            n_chunks: int, chunk: int):
    tile = ue_ref.shape[0]
    ue = ue_ref[...]
    col0 = pl.program_id(0) * tile
    if tile % 128 == 0:
        col0 = pl.multiple_of(col0, 128)

    def block_body(j, carry):
        start = j * blk
        cp = pltpu.make_async_copy(table_hbm.at[pl.ds(start, blk), :],
                                   blk_buf, sem)
        cp.start()
        cp.wait()
        # [blk, tile] = item rows x users (contract the feature dim);
        # fp32 passes on the MXU, as the f32 tables promise
        scores = jax.lax.dot_general(
            blk_buf[...], ue, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        # -0.0 -> +0.0 before any masking: one total order for ties
        scores = jnp.where(scores == 0.0, 0.0, scores)
        ids = start + jax.lax.broadcasted_iota(jnp.int32, (blk, tile), 0)
        scores = jnp.where(ids < n_items, scores, NEG_INF)

        def chunk_body(c, s):
            cp = pltpu.make_async_copy(
                seen_hbm.at[pl.ds(c * chunk, chunk), pl.ds(col0, tile)],
                seen_buf, sem)
            cp.start()
            cp.wait()

            def seen_body(l, s):
                seen = seen_buf[pl.ds(l, 1), :]                  # [1, tile]
                return jnp.where(ids == seen, NEG_INF, s)

            return jax.lax.fori_loop(0, chunk, seen_body, s)

        scores = jax.lax.fori_loop(0, n_chunks, chunk_body, scores)
        return _merge(*carry, scores, ids, k)

    init = (jnp.full((k, tile), NEG_INF, jnp.float32),
            jnp.full((k, tile), -1, jnp.int32))
    carry_s, carry_i = jax.lax.fori_loop(0, n_blocks, block_body, init)
    out_s_ref[...] = carry_s
    out_i_ref[...] = carry_i


@functools.partial(jax.jit, static_argnames=("k", "item_block", "n_items",
                                             "user_tile", "interpret"))
def fused_topk_score_pallas(ue: jax.Array, table: jax.Array,
                            seen: jax.Array, seen_mask: jax.Array, *,
                            k: int, item_block: int, n_items: int,
                            user_tile: int = DEFAULT_USER_TILE,
                            interpret: bool | None = None):
    """ue: f32[B, D]; table: f32[I, D] (HBM-resident, block-DMA'd);
    seen/seen_mask: i32/bool[B, L] padded per-user seen-item ids ->
    (scores f32[B, k], ids i32[B, k]).  interpret=None resolves from
    the backend (compiled on TPU, interpreter elsewhere)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b_in, d = ue.shape
    blk = int(min(item_block, max(n_items, 1)))
    n_blocks = math.ceil(n_items / blk)
    # one tile, or whole 128-lane tiles
    tile = b_in if b_in <= user_tile else user_tile
    b_pad = math.ceil(b_in / tile) * tile
    ue = jnp.pad(ue.astype(jnp.float32), ((0, b_pad - b_in), (0, 0)))
    # the block DMA reads n_blocks*blk rows: pad the table tail once
    tpad = n_blocks * blk - table.shape[0]
    table = jnp.pad(table.astype(jnp.float32), ((0, tpad), (0, 0)))
    # seen ids as [L, B] lane-vectors; -1 never matches an item id
    seen = jnp.where(jnp.asarray(seen_mask, bool),
                     jnp.asarray(seen, jnp.int32), -1).T
    chunk = min(SEEN_CHUNK, max(8, -(-seen.shape[0] // 8) * 8))
    n_chunks = max(1, -(-seen.shape[0] // chunk))   # >= 1: no 0-row ref
    seen = jnp.pad(seen, ((0, n_chunks * chunk - seen.shape[0]),
                          (0, b_pad - b_in)), constant_values=-1)
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    fn = pl.pallas_call(
        functools.partial(_kernel, blk=blk, n_blocks=n_blocks,
                          n_items=n_items, k=k, n_chunks=n_chunks,
                          chunk=chunk),
        grid=(b_pad // tile,),
        in_specs=[hbm, hbm, pl.BlockSpec((tile, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((k, tile), lambda i: (0, i)),
                   pl.BlockSpec((k, tile), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((k, b_pad), jnp.float32),
                   jax.ShapeDtypeStruct((k, b_pad), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32),
                        pltpu.VMEM((chunk, tile), jnp.int32),
                        pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_topk_score",
    )
    out_s, out_i = fn(table, seen, ue)
    return out_s.T[:b_in], out_i.T[:b_in]
