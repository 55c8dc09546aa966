"""Pallas TPU embedding-bag — gather + in-VMEM segment reduce.

JAX has no native EmbeddingBag; this kernel IS the substrate for the
recsys architectures (DeepFM/xDeepFM/DLRM/BERT4Rec) and mirrors the
paper's capacity-tier residency: the table [V, D] stays in HBM (on a real
deployment, possibly host memory via the TieredMemoryPlanner) and only
the rows named by the batch are DMA'd into VMEM.

Bags are fixed-length padded (ids[B, L] + mask[B, L]) — the standard TPU
formulation of ragged multi-hot lookups.  Combiner: sum or mean.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BAG_BLOCK = 8


def _kernel(ids, idmask, table_hbm, out_ref, row_buf, sem,
            *, bb: int, bag_len: int, combiner: str):
    blk = pl.program_id(0)
    out_ref[...] = jnp.zeros_like(out_ref)

    def bag_body(b, _):
        bag = blk * bb + b

        def item_body(l, cnt):
            pos = bag * bag_len + l
            live = idmask[pos] > 0
            idx = ids[pos]
            cp = pltpu.make_async_copy(table_hbm.at[pl.ds(idx, 1), :], row_buf, sem)
            cp.start()
            cp.wait()
            v = jnp.where(live, row_buf[0], 0.0)
            out_ref[b, :] = out_ref[b, :] + v
            return cnt + jnp.where(live, 1, 0)

        cnt = jax.lax.fori_loop(0, bag_len, item_body, 0, unroll=False)
        if combiner == "mean":
            denom = jnp.maximum(cnt, 1).astype(jnp.float32)
            out_ref[b, :] = out_ref[b, :] / denom
        return 0

    jax.lax.fori_loop(0, bb, bag_body, 0, unroll=False)


@functools.partial(jax.jit, static_argnames=("combiner", "bag_block", "interpret"))
def embedding_bag_pallas(table: jax.Array, ids: jax.Array, mask: jax.Array,
                         combiner: str = "sum",
                         bag_block: int = DEFAULT_BAG_BLOCK,
                         interpret: bool | None = None) -> jax.Array:
    """table: f32[V, D]; ids/mask: int32/bool[B, L] -> f32[B, D].
    interpret=None resolves from the backend (compiled on TPU,
    interpreter elsewhere)."""
    if combiner not in ("sum", "mean"):
        raise ValueError(combiner)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b_in, bag_len = ids.shape
    bb = min(bag_block, max(1, b_in))
    b_pad = ((b_in + bb - 1) // bb) * bb
    pad = b_pad - b_in
    ids_p = jnp.concatenate([ids, jnp.zeros((pad, bag_len), ids.dtype)]) if pad else ids
    mask_p = jnp.concatenate([mask, jnp.zeros((pad, bag_len), mask.dtype)]) if pad else mask
    d = table.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b_pad // bb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
        out_specs=pl.BlockSpec((bb, d), lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32),
                        pltpu.SemaphoreType.DMA],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, bb=bb, bag_len=bag_len, combiner=combiner),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b_pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=f"embedding_bag_{combiner}",
    )
    out = fn(ids_p.reshape(-1).astype(jnp.int32),
             mask_p.reshape(-1).astype(jnp.int32),
             table.astype(jnp.float32))
    return out[:b_in]
