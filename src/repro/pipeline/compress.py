"""GradCompressor — the compressed gradient combine of one pipeline.

The paper's bandwidth argument (§2, §7) applies to every slow link a
gradient crosses: the cross-device all-reduce of the sharded step and
the capacity-tier round-trip of the tiered one.  This module replaces
the exact fp32 combine with the compressed collectives of
``repro.optim.compression`` when ``repro.api.CompressionCfg.grads``
selects a scheme:

  ``int8``  each participant stochastically quantizes its share and the
            exchange is an integer psum (int8 payload, int32
            accumulate) — a real integer all-reduce in the lowered HLO,
            1/4 the bytes on the wire;
  ``topk``  each participant keeps the k = frac x size largest-|.|
            entries of its share and the exchange all-gathers (values,
            indices) — 2k entries per device instead of the dense
            tensor; colliding indices accumulate exactly.

Error feedback (``error_feedback=True``, the default) carries each
participant's compression residual into its next share, which is what
makes both schemes converge to the exact trajectory instead of to a
biased neighborhood — pinned by tests/test_compression.py.

Sharded runs emulate the per-device decomposition explicitly: the
GSPMD-combined gradient ``g`` is split into P equal shares ``g/P`` (the
shares sum to the exact gradient, so the compressed sum is a faithful
stand-in for compressing P per-device local gradients), each share adds
its device's residual slice and quantizes under its own PRNG key inside
a ``shard_map``, and the exchange runs on the mesh for real.  The
residuals live in the training state as one ``[P, *leaf.shape]`` stack
per parameter, row-sharded over the data-parallel axes like every other
large table (``state["comp"]``).  Single-device runs use the same
primitives without the mesh (one share, no collective).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.optim.compression import (dequantize_int8,
                                     psum_int8_with_residual, quantize_int8,
                                     topk_allgather_sum, topk_densify,
                                     topk_sparsify, wire_bytes)

SCHEMES = ("int8", "topk")


class GradCompressor:
    """Compressed combine: ``(grads, comp) -> (combined, comp')``.

    Pure and jit-safe — the engine calls it inside the jitted update,
    so the integer psum / top-k all-gather lowers into the same
    program as the optimizer step.  ``comp`` is the compressor's slice
    of the training state: ``{"key": PRNGKey}`` plus, under error
    feedback, ``{"ef": stacked residual tree}``.
    """

    def __init__(self, scheme: str, frac: float = 0.01,
                 error_feedback: bool = True, shard=None):
        if scheme not in SCHEMES:
            raise ValueError(f"unknown compression scheme {scheme!r}; "
                             f"known: {SCHEMES} (or 'none' = no compressor)")
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"compression frac must be in (0, 1], "
                             f"got {frac}")
        self.scheme = scheme
        self.frac = float(frac)
        self.error_feedback = bool(error_feedback)
        self.shard = shard if shard is not None and shard.is_sharded else None

    # ------------------------------------------------------------ state
    @property
    def n_shares(self) -> int:
        return self.shard.n_shards if self.shard is not None else 1

    def init_state(self, params, seed: int):
        """The ``state["comp"]`` slice: a PRNG key decorrelated from the
        model-init key, and zero residual stacks under error feedback
        ([P, *shape] per leaf — row-sharded over the mesh by the same
        shard_state rule as every other table)."""
        comp = {"key": jax.random.PRNGKey((int(seed) ^ 0x5EEDC0DE)
                                          & 0x7FFFFFFF)}
        if self.error_feedback:
            p = self.n_shares
            comp["ef"] = jax.tree.map(
                lambda g: jnp.zeros((p,) + tuple(g.shape), g.dtype), params)
        return comp

    def _zeros_ef(self, grads):
        p = self.n_shares
        return jax.tree.map(
            lambda g: jnp.zeros((p,) + tuple(g.shape), g.dtype), grads)

    # ------------------------------------------------------------ combine
    def __call__(self, grads, comp):
        key, sub = jax.random.split(comp["key"])
        ef = comp["ef"] if self.error_feedback else self._zeros_ef(grads)
        if self.shard is not None:
            combined, new_ef = self._combine_sharded(grads, ef, sub)
        else:
            combined, new_ef = self._combine_single(grads, ef, sub)
        out = {"key": key}
        if self.error_feedback:
            out["ef"] = new_ef
        return combined, out

    # ------------------------------------------------------- single-device
    def _compress_share(self, share, key):
        """One participant's (combined_contrib, residual) under the
        scheme — collective-free (the single-device path, where the
        'exchange' is the identity)."""
        if self.scheme == "int8":
            q, scale = quantize_int8(share, key)
            g_hat = dequantize_int8(q, scale)
            return g_hat, share - g_hat
        k = max(1, int(share.size * self.frac))
        vals, idx, residual = topk_sparsify(share, k)
        return topk_densify(vals, idx, share.shape), residual

    def _combine_single(self, grads, ef, key):
        leaves, treedef = jax.tree.flatten(grads)
        ef_leaves = jax.tree.flatten(ef)[0]
        outs, resids = [], []
        for i, (g, e) in enumerate(zip(leaves, ef_leaves)):
            g_hat, r = self._compress_share(g + e[0],
                                            jax.random.fold_in(key, i))
            outs.append(g_hat)
            resids.append(r[None])
        return (jax.tree.unflatten(treedef, outs),
                jax.tree.unflatten(treedef, resids))

    # ------------------------------------------------------------ sharded
    def _combine_sharded(self, grads, ef, key):
        """Per-leaf shard_map: every device compresses its share
        ``g/P + ef[d]`` under its own key and the exchange is the real
        collective on the mesh (integer psum / top-k all-gather) — the
        compressed all-reduce the lowered HLO can be asserted on."""
        mesh = self.shard.build_mesh()
        axes = self.shard.axes
        ax = axes if len(axes) > 1 else axes[0]
        p = self.shard.n_shards
        scheme, frac = self.scheme, self.frac

        leaves, treedef = jax.tree.flatten(grads)
        ef_leaves = jax.tree.flatten(ef)[0]
        outs, resids = [], []
        for i, (g, e) in enumerate(zip(leaves, ef_leaves)):
            keys = jax.random.split(jax.random.fold_in(key, i), p)

            def local(gf, ed, kd, _shape=g.shape):
                # blocks: gf full leaf (replicated), ed [1, *shape],
                # kd [1, 2] — this device's residual slice and key
                share = gf / p + ed[0]
                if scheme == "int8":
                    combined, r = psum_int8_with_residual(share, kd[0], ax)
                else:
                    k = max(1, int(share.size * frac))
                    vals, idx, r_flat = topk_sparsify(share, k)
                    combined = topk_allgather_sum(vals, idx, _shape, ax)
                    r = r_flat
                return combined, r[None]

            spec_full = P(*([None] * g.ndim))
            spec_stack = P(ax, *([None] * g.ndim))
            fn = jax.shard_map(local, mesh=mesh,
                               in_specs=(spec_full, spec_stack, P(ax, None)),
                               out_specs=(spec_full, spec_stack),
                               check_vma=False)
            combined, r = fn(g, e, keys)
            outs.append(combined)
            resids.append(r)
        return (jax.tree.unflatten(treedef, outs),
                jax.tree.unflatten(treedef, resids))

    # ------------------------------------------------------------ pricing
    def wire_bytes_per_step(self, params) -> tuple[int, int]:
        """(compressed, exact) bytes ONE participant puts on the wire
        per combine — the analytic term benchmarks scale by
        (``BENCH_compression.json``)."""
        comp = exact = 0
        for g in jax.tree.leaves(params):
            comp += wire_bytes(g.size, self.scheme, self.frac)
            exact += wire_bytes(g.size, "none")
        return comp, exact

    def describe(self) -> str:
        ef = "+ef" if self.error_feedback else ""
        tk = f" frac={self.frac}" if self.scheme == "topk" else ""
        where = f"mesh P={self.n_shares}" if self.shard is not None \
            else "single"
        return f"GradCompressor[{self.scheme}{ef}{tk}] ({where})"
