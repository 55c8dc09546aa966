"""Pallas TPU coarse-scoring kernel for the block-pruned ANN retrieval.

The serving ANN index (``repro.serving.ann.AnnIndex``) summarizes each
item *block* by an int8-quantized centroid plus a fp32 radius (the max
member distance to the centroid, inflated by the centroid's own
quantization error).  For a query ``u`` the per-block score **upper
bound**

    ub[u, b] = (u · ĉ_b) · scale_b + ‖u‖₂ · radius_b

dominates every member's exact score (Cauchy-Schwarz:
``u·x = u·c + u·(x−c) ≤ u·ĉ·s + ‖u‖(‖c−ĉ·s‖ + max‖x−c‖)``), so blocks
whose bound falls below the shortlist cut can be skipped without ever
touching their rows.  This kernel computes the whole ``[B, n_blocks]``
bound matrix in one launch: the int8 centroid table dequantizes in
VMEM, the dot rides the MXU, and the norm·radius rank-1 term is fused
into the same tile — the bound matrix is tiny (n_blocks ≈ items/1024),
which is the entire point of the coarse stage.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ue_ref, cq_ref, scale_ref, radius_ref, out_ref):
    ue = ue_ref[...]                                       # [B, D] f32
    cent = cq_ref[...].astype(jnp.float32)                 # [nb, D]
    dots = jnp.dot(ue, cent.T, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    dots = dots * scale_ref[...]                           # [1, nb] bcast
    unorm = jnp.sqrt(jnp.sum(ue * ue, axis=1, keepdims=True))
    out_ref[...] = dots + unorm * radius_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ann_block_scores_pallas(ue: jax.Array, centroids_q: jax.Array,
                            scale: jax.Array, radius: jax.Array,
                            interpret: bool | None = None) -> jax.Array:
    """ue: f32[B, D]; centroids_q: i8[nb, D]; scale/radius: f32[nb] ->
    per-block score upper bounds f32[B, nb].  interpret=None resolves
    from the backend (compiled on TPU, interpreter elsewhere)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, d = ue.shape
    nb = centroids_q.shape[0]
    # lane-align the block axis (f32 tiles are 8x128); the user axis
    # only needs sublane alignment
    nb_pad = math.ceil(nb / 128) * 128
    b_pad = math.ceil(b / 8) * 8
    ue = jnp.pad(ue.astype(jnp.float32), ((0, b_pad - b), (0, 0)))
    cq = jnp.pad(jnp.asarray(centroids_q, jnp.int8),
                 ((0, nb_pad - nb), (0, 0)))
    sc = jnp.pad(jnp.asarray(scale, jnp.float32),
                 (0, nb_pad - nb)).reshape(1, nb_pad)
    rad = jnp.pad(jnp.asarray(radius, jnp.float32),
                  (0, nb_pad - nb)).reshape(1, nb_pad)
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((b_pad, nb_pad), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
        name="ann_block_scores",
    )(ue, cq, sc, rad)
    return out[:b, :nb]
