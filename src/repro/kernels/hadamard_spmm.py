"""Pallas TPU fused Hadamard-SpMM — gather x multiply x aggregate in one pass.

The training-side half of the kernel-fusion work (serving shipped the
fused gather+score+top-K path): NGCF's per-layer message

    out[v] = sum_{e : dst_e = v}  x[x_idx_e] * y[y_idx_e]

is a gather-SDDMM ('mul') followed by an edge-aggregation SpMM, and the
intermediate [E, D] Hadamard matrix is exactly the |E|-sized stream the
paper's §4 rewrites try to keep off the capacity tier.  This kernel
fuses the three steps: per destination row block, the two source rows
of each edge are DMA'd HBM->VMEM, double-buffered so the next edge's
fetch overlaps the current multiply-accumulate; the Hadamard product
is formed *in VMEM*, and the row-block accumulator is written back to
HBM exactly once — the [E, D] message matrix never exists in HBM.  The
edge indices and row pointers stay in HBM and stream through bounded
SMEM windows (``spmm.smem_window``).

Write policy: the output keeps the SpMM side's temporal locality
(destination rows accumulate in VMEM, normal write), while the SDDMM
side's streaming store disappears entirely — its [E, D] output no
longer exists to be written.

Optional fused epilogue for NGCF's nonlinear layers: a per-node scale
(degree norm) and a leaky-relu, applied to the finished accumulator row
while it is still VMEM-resident.

``hadamard_spmm_xla`` is the production XLA route (CPU/GPU backends):
when the caller can assert structure on the index vectors — NGCF's four
call sites all can — the Hadamard factors out of the aggregation and
the XLA lowering also avoids the [E, D] intermediate:

  * ``y_is_dst``  (y_idx_e == dst_e):      out = y * spmm(gather x)
  * ``x_eq_y``    (x_idx_e == y_idx_e):    out = spmm(gather (x * y))
  * ``general``:  no structure — falls back to the naive gather/segment
                  composition (the parity oracle in ``kernels.ref``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmm import (DEFAULT_ROW_BLOCK, EDGE_CHUNK,
                                pad_to_chunks, smem_window)

STRUCTURES = ("general", "y_is_dst", "x_eq_y")


def _kernel(indptr_hbm, x_idx_hbm, y_idx_hbm, x_hbm, y_hbm, scale_ref,
            out_ref, ptr_smem, xi_smem, yi_smem, base_smem, x_buf, y_buf,
            sem_x, sem_y, idx_sem, *, rb: int, slope):
    ptr = smem_window((indptr_hbm,), (ptr_smem,), base_smem, 0,
                      (idx_sem.at[0],))
    idx = smem_window((x_idx_hbm, y_idx_hbm), (xi_smem, yi_smem), base_smem,
                      1, (idx_sem.at[1], idx_sem.at[2]))
    out_ref[...] = jnp.zeros_like(out_ref)
    row0 = pl.program_id(0) * rb

    def row_body(r, _):
        lo = ptr(row0 + r)[0]
        hi = ptr(row0 + r + 1)[0]

        def start(e, slot):
            xi, yi = idx(e)
            pltpu.make_async_copy(x_hbm.at[pl.ds(xi, 1), :], x_buf.at[slot],
                                  sem_x.at[slot]).start()
            pltpu.make_async_copy(y_hbm.at[pl.ds(yi, 1), :], y_buf.at[slot],
                                  sem_y.at[slot]).start()

        def wait(slot):
            # a wait needs only the semaphore and the transfer size
            pltpu.make_async_copy(x_hbm.at[pl.ds(0, 1), :], x_buf.at[slot],
                                  sem_x.at[slot]).wait()
            pltpu.make_async_copy(y_hbm.at[pl.ds(0, 1), :], y_buf.at[slot],
                                  sem_y.at[slot]).wait()

        @pl.when(lo < hi)
        def _warmup():
            start(lo, lo % 2)

        def edge_body(e, _):
            slot = e % 2

            # next edge's fetch overlaps this edge's multiply-accumulate
            @pl.when(e + 1 < hi)
            def _prefetch():
                start(e + 1, (e + 1) % 2)

            wait(slot)
            # the Hadamard product lives only in VMEM, never in HBM
            out_ref[r, :] = out_ref[r, :] + x_buf[slot, 0] * y_buf[slot, 0]
            return 0

        jax.lax.fori_loop(lo, hi, edge_body, 0)
        return 0

    jax.lax.fori_loop(0, rb, row_body, 0, unroll=False)
    # epilogue on the still-VMEM-resident accumulator block
    v = out_ref[...] * scale_ref[...]
    if slope is not None:
        v = jnp.where(v >= 0, v, v * slope)
    out_ref[...] = v


@functools.partial(jax.jit, static_argnames=("n_nodes", "row_block",
                                             "slope", "interpret"))
def hadamard_spmm_pallas(x: jax.Array, y: jax.Array, indptr: jax.Array,
                         x_idx: jax.Array, y_idx: jax.Array, n_nodes: int,
                         scale: jax.Array | None = None,
                         slope: float | None = None,
                         row_block: int = DEFAULT_ROW_BLOCK,
                         interpret: bool | None = None) -> jax.Array:
    """Fused gather-Hadamard-aggregate over a dst-sorted CSR.

    x: f32[N_x, D], y: f32[N_y, D] node features.
    indptr: int32[n_nodes+1] destination row pointers (dst-sorted edges).
    x_idx / y_idx: int32[E] per-edge row index into x / y.
    scale: optional f32[n_nodes] per-destination epilogue factor.
    slope: optional leaky-relu negative slope applied after ``scale``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if x_idx.shape[0] == 0:
        # no edges: every row aggregates to zero, and the epilogue maps
        # zero to zero (scale and leaky-relu both fix the origin)
        return jnp.zeros((n_nodes, x.shape[-1]), jnp.float32)
    rb = row_block
    n_pad = ((n_nodes + rb - 1) // rb) * rb
    pad = n_pad - n_nodes
    if scale is None:
        scale = jnp.ones((n_nodes,), jnp.float32)
    scale = jnp.pad(scale.astype(jnp.float32), (0, pad)).reshape(n_pad, 1)
    # padded rows repeat the last pointer: they own no edges
    indptr = jnp.pad(indptr.astype(jnp.int32), (0, pad), mode="edge")
    indptr = pad_to_chunks(indptr, indptr[-1])
    x_idx = pad_to_chunks(x_idx.astype(jnp.int32), 0)
    y_idx = pad_to_chunks(y_idx.astype(jnp.int32), 0)
    d = x.shape[-1]
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    fn = pl.pallas_call(
        functools.partial(_kernel, rb=rb, slope=slope),
        grid=(n_pad // rb,),
        in_specs=[hbm, hbm, hbm, hbm, hbm,
                  pl.BlockSpec((rb, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        scratch_shapes=[pltpu.SMEM((EDGE_CHUNK,), jnp.int32),
                        pltpu.SMEM((EDGE_CHUNK,), jnp.int32),
                        pltpu.SMEM((EDGE_CHUNK,), jnp.int32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.VMEM((2, 1, d), jnp.float32),
                        pltpu.VMEM((2, 1, d), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((3,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hadamard_spmm",
    )
    out = fn(indptr, x_idx, y_idx, x.astype(jnp.float32),
             y.astype(jnp.float32), scale)
    return out[:n_nodes]


def _epilogue(out, scale, slope):
    if scale is not None:
        out = out * scale[:, None]
    if slope is not None:
        out = jnp.where(out >= 0, out, out * slope)
    return out


def hadamard_spmm_xla(x: jax.Array, y: jax.Array, indptr: jax.Array,
                      x_idx: jax.Array, y_idx: jax.Array, n_nodes: int,
                      scale: jax.Array | None = None,
                      slope: float | None = None,
                      structure: str = "general") -> jax.Array:
    """XLA production route.  ``structure`` is a caller-asserted
    invariant on the index vectors that lets the Hadamard factor out of
    the aggregation — with it, no [E, D] intermediate is formed here
    either (the fused-NGCF jaxpr test pins that)."""
    if structure not in STRUCTURES:
        raise ValueError(f"structure must be one of {STRUCTURES}, "
                         f"got {structure!r}")
    from repro.kernels.ref import hadamard_spmm_ref, spmm_csr_ref
    if structure == "y_is_dst":
        # y rides the destination: out[v] = y[v] * sum_e x[x_idx_e]
        agg = spmm_csr_ref("sum", x.astype(jnp.float32), indptr,
                           x_idx.astype(jnp.int32), n_nodes, gather=True)
        return _epilogue(y.astype(jnp.float32) * agg, scale, slope)
    if structure == "x_eq_y":
        # both gathers share an index: the product forms at NODE level
        prod = x.astype(jnp.float32) * y.astype(jnp.float32)
        agg = spmm_csr_ref("sum", prod, indptr, x_idx.astype(jnp.int32),
                           n_nodes, gather=True)
        return _epilogue(agg, scale, slope)
    return hadamard_spmm_ref(x, y, indptr, x_idx, y_idx, n_nodes,
                             scale=scale, slope=slope)
