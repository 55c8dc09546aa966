"""Compression primitives for the slow links: collectives and storage.

Every byte moved over a capacity-tier link (Optane, PCIe host link, the
cross-device ring) is on the paper's critical path, so this module
shrinks them two ways, both with error feedback so compression error is
carried, not lost:

  * int8 stochastic-rounding quantization (4-8x byte reduction on the
    wire): q = round_s(g/scale), all-reduce int32-accumulated, dequant;
  * top-k magnitude sparsification (exchange k values + indices).

Consumers (wired by ``repro.api.CompressionCfg`` — the spec section the
engine threads through ``PipelineConfig``):

  ``pipeline.compress.GradCompressor``  — the per-step gradient
      exchange (``compression.grads``: int8 psum / top-k all-gather,
      ``ErrorFeedback`` residuals carried in the training state);
  ``memory.executor.TieredExecutor``    — int8 storage for
      capacity-tier embedding tables (``compression.embed_store``),
      fp32 dequant-on-gather via ``quantize_rows_int8``.

The roofline/fig7 collective and capacity-tier byte terms scale down by
the active scheme (``benchmarks`` emits the before/after as
``BENCH_compression.json``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "quantize_int8", "dequantize_int8", "compressed_psum_int8",
    "psum_int8_with_residual", "topk_sparsify", "topk_densify",
    "topk_allgather_sum", "quantize_rows_int8", "dequantize_rows_int8",
    "ErrorFeedback", "make_topk_compressor", "make_int8_compressor",
    "wire_bytes",
]


def quantize_int8(g: jax.Array, key: jax.Array):
    """Symmetric per-tensor int8 with stochastic rounding."""
    scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    x = g / scale
    lo = jnp.floor(x)
    p = x - lo
    r = jax.random.uniform(key, g.shape)
    q = (lo + (r < p)).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_int8(g: jax.Array, key: jax.Array, axis: str):
    """Quantize -> psum (int32 accumulate) -> dequant.  Scales are
    max-reduced so every participant dequantizes consistently."""
    scale = jax.lax.pmax(jnp.max(jnp.abs(g)) / 127.0 + 1e-12, axis)
    x = g / scale
    lo = jnp.floor(x)
    p = x - lo
    r = jax.random.uniform(key, g.shape)
    q = (lo + (r < p)).astype(jnp.int8)
    total = jax.lax.psum(q.astype(jnp.int32), axis)
    return total.astype(jnp.float32) * scale


def psum_int8_with_residual(g: jax.Array, key: jax.Array, axis):
    """``compressed_psum_int8`` that also returns the *local* residual
    ``g - dequant(q)`` — the error-feedback carry for the next step.
    Same shared pmax scale, so every participant dequantizes (and
    accounts its residual) consistently."""
    scale = jax.lax.pmax(jnp.max(jnp.abs(g)) / 127.0 + 1e-12, axis)
    x = g / scale
    lo = jnp.floor(x)
    p = x - lo
    r = jax.random.uniform(key, g.shape)
    q = lo + (r < p)
    total = jax.lax.psum(q.astype(jnp.int8).astype(jnp.int32), axis)
    return total.astype(jnp.float32) * scale, g - q * scale


def topk_allgather_sum(vals: jax.Array, idx: jax.Array, shape, axis):
    """The top-k exchange: all-gather every participant's (values,
    indices) — 2k entries per device on the wire instead of the dense
    tensor — and densify-sum them into the combined gradient.  Colliding
    indices accumulate, matching an exact sum of the sparsified
    tensors."""
    vals_all = jax.lax.all_gather(vals, axis)
    idx_all = jax.lax.all_gather(idx, axis)
    return topk_densify(vals_all.reshape(-1), idx_all.reshape(-1), shape)


def topk_sparsify(g: jax.Array, k: int):
    """Flatten, keep k largest-|.|, return (values, indices, residual)."""
    flat = g.reshape(-1)
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    kept = flat[idx]
    residual = flat.at[idx].set(0.0).reshape(g.shape)
    return kept, idx, residual


def topk_densify(vals: jax.Array, idx: jax.Array, shape) -> jax.Array:
    size = 1
    for s in shape:
        size *= s
    return jnp.zeros((size,), vals.dtype).at[idx].add(vals).reshape(shape)


class ErrorFeedback:
    """Carry compression residuals across steps: g_eff = g + e_{t-1};
    e_t = g_eff - decompress(compress(g_eff))."""

    @staticmethod
    def init(params):
        return jax.tree.map(jnp.zeros_like, params)

    @staticmethod
    def apply(grads, errors, compress_fn):
        """compress_fn(g) -> (g_hat, new_error); returns (g_hats, errors)."""
        out = jax.tree.map(lambda g, e: compress_fn(g + e), grads, errors,
                           is_leaf=lambda x: isinstance(x, jnp.ndarray))
        g_hat = jax.tree.map(lambda t: t[0], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_e = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        return g_hat, new_e


def make_topk_compressor(frac: float):
    def compress(g):
        k = max(1, int(g.size * frac))
        vals, idx, residual = topk_sparsify(g, k)
        g_hat = topk_densify(vals, idx, g.shape)
        return g_hat, residual
    return compress


def make_int8_compressor(key: jax.Array):
    holder = {"key": key}

    def compress(g):
        holder["key"], sub = jax.random.split(holder["key"])
        q, scale = quantize_int8(g, sub)
        g_hat = dequantize_int8(q, scale)
        return g_hat, g - g_hat
    return compress


# ---------------------------------------------------------------- storage
def quantize_rows_int8(table: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-row int8 for capacity-tier table *storage* (host
    side, deterministic round-to-nearest — storage must round-trip
    reproducibly, unlike the stochastic collective path).  Returns
    (q [N, D] int8, scale [N, 1] float32); max abs reconstruction error
    is scale/2 per element, so always <= the row's quantization scale."""
    table = np.asarray(table, np.float32)
    scale = (np.abs(table).max(axis=-1, keepdims=True) / 127.0
             + 1e-12).astype(np.float32)
    q = np.clip(np.rint(table / scale), -127, 127).astype(np.int8)
    return q, scale


def dequantize_rows_int8(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * scale


# ---------------------------------------------------------------- pricing
def wire_bytes(n_elements: int, scheme: str, frac: float = 0.01,
               dtype_bytes: int = 4) -> int:
    """Bytes one participant puts on the wire (or on the capacity tier)
    for an ``n_elements`` tensor under a compression scheme — the term
    the planner, roofline, and fig7 scale by.  'int8' pays 1 byte per
    element plus one fp32 scale; 'topk' pays (value + int32 index) per
    kept entry; 'none'/'fp32' pay the dense dtype."""
    if scheme in ("none", "fp32"):
        return int(n_elements) * dtype_bytes
    if scheme == "int8":
        return int(n_elements) + 4
    if scheme == "topk":
        k = max(1, int(n_elements * frac))
        return k * (dtype_bytes + 4)
    raise ValueError(f"unknown compression scheme {scheme!r}; "
                     "known: none, int8, topk")
