"""Ring SpMM — node-sharded sparse aggregation over a device ring.

The feature matrix is row-sharded across the device ring; edges are
bucketed by (destination device, ring distance to the source device).
Each ring step k, every device holds the feature block of device
(i+k) mod P (rotated by collective-permute) and applies exactly the
edge bucket whose sources live on that device.  Compute on bucket k
overlaps the permute that fetches block k+1 — the same schedule the
paper's NUMA-blocked edge placement (Fig 11) exploits, and the
distributed analogue of keeping SpMM's accumulator tier-resident (§6):
the [n_local, D] output block never leaves the device.

``n_steps < P`` gives a banded ring: only the n_steps nearest source
owners are visited, which is the locality-aware partitioning knob used
by the launch cells (REPRO_RING_STEPS); edges outside the band are
dropped by ``bucket_edges`` (acceptable when the node ordering is
community-clustered, paper Fig 11).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def bucket_edges(src: np.ndarray, dst: np.ndarray, n_nodes: int, p: int,
                 coeff: np.ndarray | None = None, n_steps: int | None = None,
                 pad_multiple: int = 8):
    """Bucket edges by (dst device, relative ring step).

    Nodes are block-partitioned: device d owns rows [d*n_local,
    (d+1)*n_local).  Bucket [d, k] holds the edges whose dst lives on d
    and whose src lives on (d+k) mod p, with *local* row indices, padded
    to a uniform size.

    ``n_nodes`` must be a multiple of ``p``; ragged graphs are padded to
    the next multiple by the shard layer (``pipeline.shard``) before
    reaching here — padded rows simply own no edges.

    One lexsort pass groups every edge into its (d, k) bucket; within a
    bucket edges keep their original order (lexsort is stable), matching
    the per-bucket ``np.nonzero`` selection of the O(P·steps) loop this
    replaced (parity pinned by tests/test_distributed.py).

    Returns (src_l, dst_l, mask, n_local) — each array [p, n_steps, E_b]
    — or (src_l, dst_l, mask, coeff_l, n_local) when ``coeff`` is given.
    """
    if n_nodes % p:
        raise ValueError(f"n_nodes {n_nodes} not divisible by {p} devices; "
                         "pad via pipeline.shard.NodePartition")
    n_local = n_nodes // p
    steps = p if n_steps is None else n_steps
    src = np.asarray(src)
    dst = np.asarray(dst)
    sdev = src // n_local
    ddev = dst // n_local
    rel = (sdev - ddev) % p
    keep = np.nonzero(rel < steps)[0]          # banded ring drops the rest
    d_k = ddev[keep]
    k_k = rel[keep]
    order = np.lexsort((k_k, d_k))             # stable: (d, k), orig order
    sel = keep[order]
    flat_bucket = d_k[order] * steps + k_k[order]
    counts = np.bincount(flat_bucket, minlength=p * steps)
    emax = max(int(counts.max()) if counts.size else 1, 1)
    emax = int(np.ceil(emax / pad_multiple)) * pad_multiple
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    within = np.arange(len(sel)) - np.repeat(starts, counts)
    slot = flat_bucket * emax + within         # position in the padded cube
    shape = (p, steps, emax)
    src_l = np.zeros(shape, np.int32)
    dst_l = np.zeros(shape, np.int32)
    mask = np.zeros(shape, bool)
    src_l.reshape(-1)[slot] = src[sel] % n_local
    dst_l.reshape(-1)[slot] = dst[sel] % n_local
    mask.reshape(-1)[slot] = True
    if coeff is not None:
        coeff_l = np.zeros(shape, np.float32)
        coeff_l.reshape(-1)[slot] = np.asarray(coeff)[sel]
        return src_l, dst_l, mask, coeff_l, n_local
    return src_l, dst_l, mask, n_local


def _bucket_edges_loop(src, dst, n_nodes: int, p: int, coeff=None,
                       n_steps: int | None = None, pad_multiple: int = 8):
    """The original O(P·steps) per-bucket selection loop, kept as the
    layout oracle for the vectorized ``bucket_edges`` (parity test)."""
    if n_nodes % p:
        raise ValueError(f"n_nodes {n_nodes} not divisible by {p} devices")
    n_local = n_nodes // p
    steps = p if n_steps is None else n_steps
    src = np.asarray(src)
    dst = np.asarray(dst)
    sdev = src // n_local
    ddev = dst // n_local
    rel = (sdev - ddev) % p
    keep = rel < steps
    buckets: dict[tuple[int, int], np.ndarray] = {}
    emax = 1
    for d in range(p):
        for k in range(steps):
            sel = np.nonzero((ddev == d) & (rel == k) & keep)[0]
            buckets[(d, k)] = sel
            emax = max(emax, len(sel))
    emax = int(np.ceil(emax / pad_multiple)) * pad_multiple
    shape = (p, steps, emax)
    src_l = np.zeros(shape, np.int32)
    dst_l = np.zeros(shape, np.int32)
    mask = np.zeros(shape, bool)
    coeff_l = np.zeros(shape, np.float32) if coeff is not None else None
    for (d, k), sel in buckets.items():
        e = len(sel)
        src_l[d, k, :e] = src[sel] % n_local
        dst_l[d, k, :e] = dst[sel] % n_local
        mask[d, k, :e] = True
        if coeff_l is not None:
            coeff_l[d, k, :e] = np.asarray(coeff)[sel]
    if coeff_l is not None:
        return src_l, dst_l, mask, coeff_l, n_local
    return src_l, dst_l, mask, n_local


def make_ring_spmm(mesh, axis, n_local: int, with_coeff: bool = False,
                   n_steps: int | None = None, relative_buckets: bool = True,
                   quantize: bool = False):
    """Build ring_spmm(x, src_l, dst_l, mask[, coeff]) -> A @ x over the
    flattened device ring of ``axis`` (one name or a tuple of names).

    x: [N, D] row-sharded on ``axis``; bucket arrays [P, S, E_b] sharded
    on their leading (dst-device) dim, as produced by ``bucket_edges``
    (which emits relative buckets — ``relative_buckets`` is accepted for
    signature stability and must stay True).

    ``quantize=True`` rotates an int8 payload instead of the fp32 block
    (``repro.api.CompressionCfg.ring``): each device quantizes its local
    block ONCE (symmetric per-block int8, deterministic round-to-nearest
    so forward and transpose rings see identical payloads) and the ring
    permutes (q int8, scale fp32 scalar) — 1/4 the bytes per rotation.
    The k=0 bucket still gathers from the exact local block, so local
    edges (the majority under community-clustered node orderings, paper
    Fig 11) see zero quantization error; only remote contributions pay
    the bounded <= scale/2 per-element rounding.  Per-step error
    feedback does not apply here — the payload is an *activation*
    rotated once per call, with no next step to carry a residual into;
    the gradient path's residuals live in ``pipeline.compress``.
    """
    if not relative_buckets:
        raise NotImplementedError("absolute bucket indexing was retired; "
                                  "bucket_edges emits relative buckets")
    axes = axis if isinstance(axis, (tuple, list)) else (axis,)
    axes = tuple(axes)
    p = int(np.prod([mesh.shape[a] for a in axes]))
    steps = p if n_steps is None else n_steps

    def local_fn(x, src_l, dst_l, mask, coeff=None):
        # shard_map blocks: x [n_local, D]; buckets [1, S, E_b]
        src_l = src_l[0]
        dst_l = dst_l[0]
        mask = mask[0]
        if coeff is not None:
            coeff = coeff[0]
        perm = [(j, (j - 1) % p) for j in range(p)]
        ax = axes if len(axes) > 1 else axes[0]

        def gather(k, x_rot, bs, bm):
            if quantize:
                q_rot, s_rot = x_rot
                deq = q_rot.astype(jnp.float32) * s_rot
                # the local (k=0) bucket reads the exact resident block
                xk = jnp.where(k == 0, x, deq)
            else:
                xk = x_rot
            return jnp.where(bm[:, None], xk[bs], 0.0)

        def rotate(x_rot):
            if quantize:
                q_rot, s_rot = x_rot
                return (jax.lax.ppermute(q_rot, ax, perm),
                        jax.lax.ppermute(s_rot, ax, perm))
            return jax.lax.ppermute(x_rot, ax, perm)

        def body(k, carry):
            acc, x_rot = carry
            bs = jax.lax.dynamic_index_in_dim(src_l, k, 0, keepdims=False)
            bd = jax.lax.dynamic_index_in_dim(dst_l, k, 0, keepdims=False)
            bm = jax.lax.dynamic_index_in_dim(mask, k, 0, keepdims=False)
            m = gather(k, x_rot, bs, bm)
            if coeff is not None:
                bc = jax.lax.dynamic_index_in_dim(coeff, k, 0, keepdims=False)
                m = m * bc[:, None]
            acc = acc + jax.ops.segment_sum(m, bd, num_segments=n_local)
            # rotate: after this permute device i holds block (i+k+1)%p
            return acc, rotate(x_rot)

        if quantize:
            scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
            q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
            payload = (q, scale)
        else:
            payload = x
        # the carry must vary over the ring axes like the permuted
        # payload it accumulates, or the loop's carry types disagree
        acc = jax.lax.pcast(jnp.zeros((n_local, x.shape[-1]), x.dtype),
                            axes, to="varying")
        acc, _ = jax.lax.fori_loop(0, steps, body, (acc, payload))
        return acc

    xspec = P(axes if len(axes) > 1 else axes[0], None)
    bspec = P(axes if len(axes) > 1 else axes[0], None, None)
    in_specs = (xspec, bspec, bspec, bspec) + ((bspec,) if with_coeff else ())
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=xspec)

    if with_coeff:
        def ring(x, src_l, dst_l, mask, coeff):
            return fn(x, src_l, dst_l, mask, coeff)
    else:
        def ring(x, src_l, dst_l, mask):
            return fn(x, src_l, dst_l, mask)
    return ring
