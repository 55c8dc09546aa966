"""Pallas TPU SpMM — CSR row-block aggregation with a VMEM accumulator.

TPU adaptation of the paper's write-policy finding (§6): SpMM *does* have
temporal locality — the destination row is touched once per incoming edge
— so unlike SDDMM the kernel keeps the output row block resident in VMEM
for the whole contraction and writes it back to HBM exactly once
("normal write" behaviour; nt-write would destroy the accumulator reuse,
the paper measured >20x slowdown).

Structure:
  edges are pre-sorted by destination (CSR).  ``indptr`` and the sorted
  source indices stay in HBM and stream through bounded SMEM windows of
  ``EDGE_CHUNK`` entries (``smem_window``) that refill whenever the row
  or edge cursor leaves them; rows and edges ascend across the whole
  grid, so each window is loaded about once.  SMEM holds a few KiB
  whatever the graph size.  The
  message matrix (or, with gather=True, the node-feature matrix) stays
  in HBM and rows are DMA'd per edge into a small VMEM buffer; the out
  row-block [RB, D] is the VMEM accumulator.

Reduces: 'sum' (used by NGCF/LightGCN/GCN) and 'max' (generalized SpMM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_ROW_BLOCK = 8
EDGE_CHUNK = 2048          # int32 edge indices per SMEM window (8 KiB)


def pad_to_chunks(a, fill):
    """Pad a 1-D index array to a whole number of SMEM windows, so every
    window DMA is aligned and in bounds (HBM int32 vectors are tiled in
    1024-element units)."""
    extra = -a.shape[0] % EDGE_CHUNK
    return jnp.pad(a, (0, extra), constant_values=fill) if extra else a


def smem_window(hbms, smems, base_ref, slot: int, sems):
    """Bounded, chunk-aligned SMEM window over 1-D HBM index arrays.

    Returns ``read(pos) -> tuple of a[pos]`` (one per array; array i
    copies on DMA semaphore ``sems[i]``).  The window covers
    ``[base_ref[slot], base_ref[slot] + EDGE_CHUNK)`` and refills (one
    synchronous DMA per array) when ``pos`` falls outside it.  Positions
    ascend over the whole grid, so the window is initialized once, at
    grid step 0, persists across steps, and loads each chunk about once.
    The arrays must be padded to a multiple of ``EDGE_CHUNK``."""
    chunk = EDGE_CHUNK

    @pl.when(pl.program_id(0) == 0)
    def _init():
        base_ref[slot] = -chunk          # forces a load at the first read

    def read(pos):
        base = base_ref[slot]

        @pl.when((pos < base) | (pos >= base + chunk))
        def _refill():
            new = pl.multiple_of((pos // chunk) * chunk, chunk)
            copies = [pltpu.make_async_copy(h.at[pl.ds(new, chunk)], s, sem)
                      for h, s, sem in zip(hbms, smems, sems)]
            for c in copies:
                c.start()
            for c in copies:
                c.wait()
            base_ref[slot] = new

        off = pos - base_ref[slot]
        return tuple(s[off] for s in smems)

    return read


def _kernel(indptr_hbm, src_hbm, x_hbm, out_ref, ptr_smem, idx_smem,
            base_smem, row_buf, sem, idx_sem, *, reduce: str, rb: int,
            gather: bool):
    ptr = smem_window((indptr_hbm,), (ptr_smem,), base_smem, 0,
                      (idx_sem.at[0],))
    if gather:
        src = smem_window((src_hbm,), (idx_smem,), base_smem, 1,
                          (idx_sem.at[1],))
    init = 0.0 if reduce == "sum" else -jnp.inf
    out_ref[...] = jnp.full_like(out_ref, init)
    row0 = pl.program_id(0) * rb

    def row_body(r, _):
        lo = ptr(row0 + r)[0]
        hi = ptr(row0 + r + 1)[0]

        # double-buffered row DMA: two VMEM row buffers + two
        # semaphores ping-pong over the edge loop, so edge e+1's fetch
        # overlaps edge e's accumulate instead of serializing on one
        # start();wait() pair
        def start(e, slot):
            idx = src(e)[0] if gather else e
            pltpu.make_async_copy(x_hbm.at[pl.ds(idx, 1), :],
                                  row_buf.at[slot], sem.at[slot]).start()

        def wait(slot):
            # a wait needs only the semaphore and the transfer size
            pltpu.make_async_copy(x_hbm.at[pl.ds(0, 1), :],
                                  row_buf.at[slot], sem.at[slot]).wait()

        @pl.when(lo < hi)
        def _warmup():
            start(lo, lo % 2)

        def edge_body(e, _):
            slot = e % 2

            @pl.when(e + 1 < hi)
            def _prefetch():
                start(e + 1, (e + 1) % 2)

            wait(slot)
            v = row_buf[slot, 0]
            if reduce == "sum":
                out_ref[r, :] = out_ref[r, :] + v
            else:
                out_ref[r, :] = jnp.maximum(out_ref[r, :], v)
            return 0

        jax.lax.fori_loop(lo, hi, edge_body, 0)
        return 0

    jax.lax.fori_loop(0, rb, row_body, 0, unroll=False)
    if reduce == "max":  # empty rows: -inf -> 0 (matches XLA oracle)
        out_ref[...] = jnp.where(jnp.isfinite(out_ref[...]), out_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("reduce", "n_nodes", "row_block",
                                             "gather", "interpret"))
def spmm_csr_pallas(reduce: str, values: jax.Array, indptr: jax.Array,
                    src_sorted: jax.Array, n_nodes: int,
                    row_block: int = DEFAULT_ROW_BLOCK,
                    gather: bool = False,
                    interpret: bool | None = None) -> jax.Array:
    """CSR SpMM.

    values: f32[E, D] per-edge messages (gather=False) or f32[N_src, D]
      node features gathered through ``src_sorted`` (gather=True).
    indptr: int32[n_nodes+1] destination row pointers over dst-sorted edges.
    src_sorted: int32[E] source index per dst-sorted edge (used iff gather).
    interpret: None resolves from the backend (compiled on TPU,
      interpreter elsewhere), so direct callers bypassing ``kernels.ops``
      don't silently run interpreter-mode Pallas on TPU.
    """
    if reduce not in ("sum", "max"):
        raise ValueError(reduce)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d = values.shape[-1]
    n_edges = src_sorted.shape[0] if gather else values.shape[0]
    if n_edges == 0:
        # no edges: every row is empty, and both reduces map empty to 0
        return jnp.zeros((n_nodes, d), jnp.float32)
    rb = row_block
    n_pad = ((n_nodes + rb - 1) // rb) * rb
    # padded rows repeat the last pointer: they own no edges
    indptr = jnp.pad(indptr.astype(jnp.int32), (0, n_pad - n_nodes),
                     mode="edge")
    indptr = pad_to_chunks(indptr, indptr[-1])
    src_sorted = src_sorted.astype(jnp.int32)
    if gather:
        src_sorted = pad_to_chunks(src_sorted, 0)
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    fn = pl.pallas_call(
        functools.partial(_kernel, reduce=reduce, rb=rb, gather=gather),
        grid=(n_pad // rb,),
        in_specs=[hbm, hbm, hbm],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        scratch_shapes=[pltpu.SMEM((EDGE_CHUNK,), jnp.int32),
                        pltpu.SMEM((EDGE_CHUNK,), jnp.int32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.VMEM((2, 1, d), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=f"spmm_{reduce}",
    )
    out = fn(indptr, src_sorted, values.astype(jnp.float32))
    return out[:n_nodes]


def build_csr_by_dst(dst: np.ndarray, src: np.ndarray, n_nodes: int,
                     edge_mask: np.ndarray | None = None):
    """Host-side helper: sort edges by dst, build indptr.  Masked (padded)
    edges are dropped.  Returns (indptr, src_sorted, perm)."""
    dst = np.asarray(dst)
    src = np.asarray(src)
    if edge_mask is not None:
        keep = np.asarray(edge_mask).astype(bool)
        dst, src = dst[keep], src[keep]
        perm_base = np.nonzero(keep)[0]
    else:
        perm_base = np.arange(len(dst))
    order = np.argsort(dst, kind="stable")
    indptr = np.zeros(n_nodes + 1, dtype=np.int32)
    np.add.at(indptr, dst[order] + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, src[order].astype(np.int32), perm_base[order]
