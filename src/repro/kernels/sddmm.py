"""Pallas TPU SDDMM — per-edge sampled dense-dense op, streaming store.

TPU adaptation of the paper's nt-write guidance (§6): SDDMM output (the
per-edge message matrix) has *no temporal locality* — each edge row is
produced once and never re-read by this kernel — so the kernel streams
each output block straight back to HBM and keeps **no VMEM-resident
accumulator**.  This is the TPU-native analogue of a non-temporal store
bypassing the cache hierarchy.

Structure:
  grid = (E_pad / EDGE_BLOCK,)  with ``dimension_semantics=arbitrary``
  src/dst/edge-mask (+ optional per-edge coeff) are scalar-prefetched to
  SMEM; the node-feature matrix stays in HBM and rows are DMA'd on demand
  into a double-buffered VMEM scratch pair.

Supported ops (mirrors core.sparse_ops.sddmm):
  'mul'  : m_e = x[src_e] * y[dst_e]            out [E, D]
  'add'  : m_e = x[src_e] + y[dst_e]            out [E, D]
  'dot'  : m_e = <x[src_e], y[dst_e]>           out [E, 1]
  'copy' : m_e = coeff_e * x[src_e]             out [E, D]  (coeff=1 if None)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_EDGE_BLOCK = 128


def _kernel(src_idx, dst_idx, emask, coeff, x_hbm, y_hbm, out_ref,
            a_buf, b_buf, sem_a, sem_b, *, op: str, eb: int):
    blk = pl.program_id(0)

    def body(i, _):
        e = blk * eb + i
        s = src_idx[e]
        ca = pltpu.make_async_copy(x_hbm.at[pl.ds(s, 1), :], a_buf, sem_a)
        ca.start()
        if op in ("mul", "add", "dot"):
            d = dst_idx[e]
            cb = pltpu.make_async_copy(y_hbm.at[pl.ds(d, 1), :], b_buf, sem_b)
            cb.start()
            ca.wait()
            cb.wait()
            a, b = a_buf[0], b_buf[0]
            if op == "mul":
                m = a * b
            elif op == "add":
                m = a + b
            else:  # dot
                m = jnp.sum(a * b)
        else:  # copy (optionally scaled)
            ca.wait()
            m = a_buf[0] * coeff[e]
        live = emask[e] > 0
        if op == "dot":
            out_ref[i, 0] = jnp.where(live, m, 0.0)
        else:
            out_ref[i, :] = jnp.where(live, m, 0.0)
        return 0

    jax.lax.fori_loop(0, eb, body, 0, unroll=False)


def _pad_to(arr, n, fill=0):
    pad = n - arr.shape[0]
    if pad == 0:
        return arr
    return jnp.concatenate([arr, jnp.full((pad,) + arr.shape[1:], fill, arr.dtype)])


@functools.partial(jax.jit, static_argnames=("op", "edge_block", "interpret"))
def sddmm_pallas(op: str, x: jax.Array, y: jax.Array, src: jax.Array,
                 dst: jax.Array, edge_mask: jax.Array,
                 coeff: jax.Array | None = None,
                 edge_block: int = DEFAULT_EDGE_BLOCK,
                 interpret: bool | None = None) -> jax.Array:
    """Pallas SDDMM.  x, y: f32[N, D]; src/dst: int32[E]; returns
    f32[E, D] (or f32[E] for op='dot').  interpret=None resolves from
    the backend (compiled on TPU, interpreter elsewhere)."""
    if op not in ("mul", "add", "dot", "copy"):
        raise ValueError(op)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    e_in = src.shape[0]
    eb = min(edge_block, max(8, e_in))
    e_pad = ((e_in + eb - 1) // eb) * eb
    src_p = _pad_to(src.astype(jnp.int32), e_pad)
    dst_p = _pad_to(dst.astype(jnp.int32), e_pad)
    mask_p = _pad_to(edge_mask.astype(jnp.int32), e_pad)
    if coeff is None:
        coeff_p = jnp.ones((e_pad,), jnp.float32)
    else:
        coeff_p = _pad_to(coeff.astype(jnp.float32), e_pad)

    d = x.shape[-1]
    out_d = 1 if op == "dot" else d
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(e_pad // eb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
                  pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
        # streaming store: each out block written exactly once (nt-write analog)
        out_specs=pl.BlockSpec((eb, out_d), lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32),
                        pltpu.VMEM((1, d), jnp.float32),
                        pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, op=op, eb=eb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e_pad, out_d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=f"sddmm_{op}",
    )
    out = fn(src_p, dst_p, mask_p, coeff_p, x.astype(jnp.float32),
             y.astype(jnp.float32))
    out = out[:e_in]
    return out[:, 0] if op == "dot" else out
