"""Pallas TPU SpMM — CSR row-block aggregation with a VMEM accumulator.

TPU adaptation of the paper's write-policy finding (§6): SpMM *does* have
temporal locality — the destination row is touched once per incoming edge
— so unlike SDDMM the kernel keeps the output row block resident in VMEM
for the whole contraction and writes it back to HBM exactly once
("normal write" behaviour; nt-write would destroy the accumulator reuse,
the paper measured >20x slowdown).

Structure:
  edges are pre-sorted by destination (CSR).  ``indptr`` and the sorted
  source indices stay in HBM.  The message matrix (or, with
  gather=True, the node-feature matrix) stays in HBM too, and each
  edge's row is DMA'd into one slot of a VMEM ring: edge ``e`` owns
  slot ``e % depth`` and, once its row has landed, starts the fetch of
  edge ``e + depth - 1``.  The ring is primed once, at grid step 0, runs
  over the call's whole edge stream, across row and block boundaries
  (the grid is sequential and scratch persists across its steps), and
  drains once, after the last edge, so ``depth - 1`` row fetches stay in
  flight and the loop is not bound by one fetch's latency.  Row
  pointers and source indices are read at positions that ascend one at
  a time, through SMEM streams of two ``EDGE_CHUNK`` halves
  (``smem_stream``) that load each chunk once and are checked once per
  ``EDGE_UNROLL`` edges, not per read; SMEM holds a few KiB whatever
  the graph size.  Each destination row reduces its edges in CSR order
  in a carried value and is stored once into the out row block [RB, D],
  the VMEM accumulator written back to HBM once per block.
  ``ring_plan`` picks the row block and the depth from the call's
  static shapes.

Reduces: 'sum' (used by NGCF/LightGCN/GCN) and 'max' (generalized SpMM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

DEFAULT_ROW_BLOCK = 8
EDGE_CHUNK = 2048          # int32 edge indices per SMEM window (8 KiB)
MAX_ROW_BLOCK = 256        # destination rows per SpMM grid step, at most
RING_BYTES = 128 * 1024    # VMEM the SpMM's row-fetch ring may hold
MAX_RING_DEPTH = 32        # row fetches in the SpMM's DMA ring, at most
EDGE_UNROLL = 8            # edges a trip of the SpMM's edge loop


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


def smem_stream(hbm, smem, loaded_ref, slot: int, sem):
    """SMEM reads of a 1-D HBM index array at positions that ascend one
    at a time over the whole grid.

    ``smem`` holds two ``EDGE_CHUNK`` halves, chunk ``c`` in half
    ``c % 2``; ``loaded_ref[slot]`` is the last chunk loaded.
    ``ensure(q)`` loads the chunk of ``q`` if it is new (one synchronous
    DMA on ``sem``), and ``get(p)`` then reads any position from the
    previous chunk's start up to ``q`` with no check, so a caller
    ensures once for a run of reads.  The array must be padded to a
    multiple of ``EDGE_CHUNK``."""
    chunk = EDGE_CHUNK

    @pl.when(pl.program_id(0) == 0)
    def _init():
        loaded_ref[slot] = -1

    def ensure(q):
        c = q // chunk

        @pl.when(c > loaded_ref[slot])
        def _load():
            half = pl.multiple_of((c % 2) * chunk, chunk)
            copy = pltpu.make_async_copy(
                hbm.at[pl.ds(pl.multiple_of(c * chunk, chunk), chunk)],
                smem.at[pl.ds(half, chunk)], sem)
            copy.start()
            copy.wait()
            loaded_ref[slot] = c

    def get(p):
        return smem[p & (2 * chunk - 1)]

    return ensure, get


def _kernel(indptr_hbm, src_hbm, x_hbm, out_ref, ptr_smem,
            idx_smem, loaded_smem, row_buf, sem, idx_sem, *, reduce: str,
            rb: int, depth: int, gather: bool):
    ptr_ensure, ptr = smem_stream(indptr_hbm, ptr_smem, loaded_smem, 0,
                                  idx_sem.at[0])
    if gather:
        src_ensure, src = smem_stream(src_hbm, idx_smem, loaded_smem, 1,
                                      idx_sem.at[1])
    else:
        def src_ensure(q):
            pass
    ahead = depth - 1
    last_row = x_hbm.shape[0] - 1

    # edge e owns ring slot e % depth (depth is a power of two).  Fetches
    # run `ahead` edges past the stream's end, unconditionally: the
    # source indices are padded there, and per-edge rows are clamped
    def start(e):
        idx = src(e) if gather else jnp.minimum(e, last_row)
        slot = e & (depth - 1)
        pltpu.make_async_copy(x_hbm.at[pl.ds(idx, 1), :], row_buf.at[slot],
                              sem.at[slot]).start()

    def wait(e):
        # a wait needs only the semaphore and the transfer size
        slot = e & (depth - 1)
        pltpu.make_async_copy(x_hbm.at[pl.ds(0, 1), :], row_buf.at[slot],
                              sem.at[slot]).wait()

    def each(lo, hi, fn):
        def body(e, carry):
            fn(e)
            return carry

        jax.lax.fori_loop(lo, hi, body, 0)

    row0 = pl.program_id(0) * rb
    ptr_ensure(row0)
    lo0 = ptr(row0)

    # the ring runs over the call's whole edge stream: primed once, then
    # every edge starts the fetch `ahead` edges on, across row and block
    # boundaries, and it drains once, after the last edge
    @pl.when(pl.program_id(0) == 0)
    def _prime():
        def prime(e):
            src_ensure(e)
            start(e)

        each(lo0, lo0 + ahead, prime)

    def edge(e, acc):
        wait(e)
        start(e + ahead)            # into the slot edge e - 1 has read
        v = row_buf[e & (depth - 1), 0]
        return acc + v if reduce == "sum" else jnp.maximum(acc, v)

    def edges(e, acc):              # EDGE_UNROLL edges, one index check
        src_ensure(e + ahead + EDGE_UNROLL - 1)
        for j in range(EDGE_UNROLL):
            acc = edge(e + j, acc)
        return acc

    def one_edge(e, acc):
        src_ensure(e + ahead)
        return edge(e, acc)

    init = 0.0 if reduce == "sum" else -jnp.inf

    def row_body(r, lo):
        ptr_ensure(row0 + r + 1)
        hi = ptr(row0 + r + 1)
        whole = lo + (hi - lo) // EDGE_UNROLL * EDGE_UNROLL
        acc = jnp.full(out_ref.shape[1:], init, out_ref.dtype)
        acc = jax.lax.fori_loop(0, (whole - lo) // EDGE_UNROLL,
                                lambda t, a: edges(lo + t * EDGE_UNROLL, a),
                                acc)
        acc = jax.lax.fori_loop(whole, hi, one_edge, acc)
        if reduce == "max":  # empty rows: -inf -> 0 (matches XLA oracle)
            acc = jnp.where(jnp.isfinite(acc), acc, 0.0)
        out_ref[r, :] = acc
        return hi

    end = jax.lax.fori_loop(0, rb, row_body, lo0)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _drain():
        each(end, end + ahead, wait)


def ring_plan(n_nodes: int, d: int) -> tuple[int, int]:
    """(row_block, ring depth) of a call, from its static shapes.

    Depth: on a v5e at D=128 one row fetch from an HBM table takes about
    310 ns (the edge time with two slots, one fetch in flight at a wait)
    and the edge loop about 19 ns, so some 17 fetches must be in flight;
    the loop stops waiting at 32 slots (18.6 ns an edge, the same at 64,
    23 ns at 16).  More slots cost VMEM and no time, so the ring takes as
    many as ``RING_BYTES`` holds, up to ``MAX_RING_DEPTH``, as a power of
    two; a (1, D) f32 slot pads to 8 sublanes.  The edge count does not
    enter: the ring is primed and drained once per call, whatever its
    length.
    Row block: up to ``MAX_ROW_BLOCK`` destination rows, in whole
    8-row tiles, so a large graph takes few grid steps and a small one
    pads little."""
    slot = 8 * 4 * (-(-d // 128) * 128)
    fits = max(RING_BYTES // slot, 2)
    depth = min(MAX_RING_DEPTH, 1 << (fits.bit_length() - 1))
    return min(MAX_ROW_BLOCK, -(-n_nodes // 8) * 8), depth


@functools.partial(jax.jit, static_argnames=("reduce", "n_nodes", "row_block",
                                             "gather", "interpret"))
def spmm_csr_pallas(reduce: str, values: jax.Array, indptr: jax.Array,
                    src_sorted: jax.Array, n_nodes: int,
                    row_block: int | None = None,
                    gather: bool = False,
                    interpret: bool | None = None) -> jax.Array:
    """CSR SpMM.

    values: f32[E, D] per-edge messages (gather=False) or f32[N_src, D]
      node features gathered through ``src_sorted`` (gather=True).
    indptr: int32[n_nodes+1] destination row pointers over dst-sorted edges.
    src_sorted: int32[E] source index per dst-sorted edge (used iff gather).
    row_block: destination rows per grid step; None takes ``ring_plan``'s.
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
    rb, depth = ring_plan(n_nodes, d)
    rb = row_block or rb
    obs.count("spmm_inflight", depth)
    n_pad = ((n_nodes + rb - 1) // rb) * rb
    # padded rows repeat the last pointer: they own no edges
    indptr = jnp.pad(indptr.astype(jnp.int32), (0, n_pad - n_nodes),
                     mode="edge")
    indptr = pad_to_chunks(indptr, indptr[-1])
    src_sorted = src_sorted.astype(jnp.int32)
    if gather:
        # the ring reads `depth - 1` indices past the last edge
        src_sorted = pad_to_chunks(jnp.pad(src_sorted, (0, depth)), 0)
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    fn = pl.pallas_call(
        functools.partial(_kernel, reduce=reduce, rb=rb, depth=depth,
                          gather=gather),
        grid=(n_pad // rb,),
        in_specs=[hbm, hbm, hbm],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        scratch_shapes=[pltpu.SMEM((2 * EDGE_CHUNK,), jnp.int32),
                        pltpu.SMEM((2 * EDGE_CHUNK,), jnp.int32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.VMEM((depth, 1, d), jnp.float32),
                        pltpu.SemaphoreType.DMA((depth,)),
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
