"""Ring SpMM — node-sharded sparse aggregation over a device ring.

The feature matrix is row-sharded across the device ring; edges are
bucketed by (destination device, ring distance to the source device)
into dst-sorted CSR buckets.  Each ring step k, every device holds the
feature block of device (i+k) mod P (rotated by collective-permute),
aggregates exactly the edge bucket whose sources live on that device
with one gather-SpMM call (the Pallas DMA-ring kernel on TPU) into its
[n_local, D] output block, then permutes the block on.  The permute
issues after the bucket's aggregation; it is not double-buffered, so
little of it hides under compute.  The schedule is the paper's
NUMA-blocked edge placement (Fig 11) as a device ring, and the
distributed analogue of keeping SpMM's accumulator tier-resident (§6):
the output block never leaves the device.

``n_steps < P`` gives a banded ring: only the n_steps nearest source
owners are visited, which is the locality-aware partitioning knob used
by the launch cells (REPRO_RING_STEPS); edges outside the band are
dropped by ``bucket_edges`` (acceptable when the node ordering is
community-clustered, paper Fig 11).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.kernels import ops as kops


def bucket_edges(src: np.ndarray, dst: np.ndarray, n_nodes: int, p: int,
                 coeff: np.ndarray | None = None, n_steps: int | None = None,
                 pad_multiple: int = 8):
    """Bucket edges by (dst device, relative ring step) into CSR buckets.

    Nodes are block-partitioned: device d owns rows [d*n_local,
    (d+1)*n_local).  Bucket [d, k] holds the edges whose dst lives on d
    and whose src lives on (d+k) mod p, sorted by *local* dst row, with
    local source rows, padded to a uniform length.  ``indptr[d, k]``
    holds the bucket's row pointers: the edges of local row r are
    slots ``indptr[d, k, r]:indptr[d, k, r + 1]``, and slots past
    ``indptr[d, k, -1]`` are padding (source row 0, coefficient 0).

    ``n_nodes`` must be a multiple of ``p``; ragged graphs are padded to
    the next multiple by the shard layer (``pipeline.shard``) before
    reaching here — padded rows simply own no edges.

    One stable sort over a combined (bucket, local dst row) key groups
    and orders every edge; edges of one row keep their original order.

    Returns (src_l [p, n_steps, E_b], indptr [p, n_steps, n_local + 1],
    n_local), or (src_l, indptr, coeff_l, n_local) when ``coeff`` is
    given.
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
    n_rows = p * steps * n_local               # (bucket, local row) keys
    key = (ddev[keep] * steps + rel[keep]) * n_local + dst[keep] % n_local
    order = np.argsort(key, kind="stable")
    sel = keep[order]
    per_row = np.bincount(key, minlength=n_rows).reshape(p * steps, n_local)
    counts = per_row.sum(1)
    emax = max(int(counts.max()) if counts.size else 1, 1)
    emax = int(np.ceil(emax / pad_multiple)) * pad_multiple
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    within = np.arange(len(sel)) - np.repeat(starts, counts)
    slot = key[order] // n_local * emax + within   # place in the padded cube
    shape = (p, steps, emax)
    src_l = np.zeros(shape, np.int32)
    src_l.reshape(-1)[slot] = src[sel] % n_local
    indptr = np.zeros((p * steps, n_local + 1), np.int32)
    np.cumsum(per_row, axis=1, out=indptr[:, 1:])
    indptr = indptr.reshape(p, steps, n_local + 1)
    if coeff is not None:
        coeff_l = np.zeros(shape, np.float32)
        coeff_l.reshape(-1)[slot] = np.asarray(coeff)[sel]
        return src_l, indptr, coeff_l, n_local
    return src_l, indptr, n_local


def make_ring_spmm(mesh, axis, n_local: int, with_coeff: bool = False,
                   n_steps: int | None = None, quantize: bool = False,
                   impl: str = "xla"):
    """Build ring_spmm(x, src_l, indptr[, coeff]) -> A @ x over the
    flattened device ring of ``axis`` (one name or a tuple of names).

    x: [N, D] row-sharded on ``axis``; bucket arrays [P, S, ...] sharded
    on their leading (dst-device) dim, as produced by ``bucket_edges``.

    Each ring step aggregates its bucket with one gather-SpMM call
    (``kernels.ops.spmm_csr``, dispatched by ``impl``: the Pallas
    DMA-ring kernel on TPU, the XLA oracle elsewhere) into the carried
    [n_local, D] accumulator.  With ``with_coeff`` the bucket's edges
    carry a coefficient, which the Pallas kernel has no operand for:
    such a ring forms the weighted messages and sums them on the XLA
    route whatever ``impl`` says.  The route is counted at trace time
    (``repro.obs`` ``ring_route``).

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
    axes = axis if isinstance(axis, (tuple, list)) else (axis,)
    axes = tuple(axes)
    p = int(np.prod([mesh.shape[a] for a in axes]))
    steps = p if n_steps is None else n_steps
    route = "xla" if with_coeff else impl

    def local_fn(x, src_l, indptr, coeff=None):
        # shard_map blocks: x [n_local, D]; buckets [1, S, ...]
        obs.count("ring_route", route)
        src_l = src_l[0]
        indptr = indptr[0]
        if coeff is not None:
            coeff = coeff[0]
        perm = [(j, (j - 1) % p) for j in range(p)]
        ax = axes if len(axes) > 1 else axes[0]

        def block(k, x_rot):
            if quantize:
                q_rot, s_rot = x_rot
                deq = q_rot.astype(jnp.float32) * s_rot
                # the local (k=0) bucket reads the exact resident block
                return jnp.where(k == 0, x, deq)
            return x_rot

        def rotate(x_rot):
            if quantize:
                q_rot, s_rot = x_rot
                return (jax.lax.ppermute(q_rot, ax, perm),
                        jax.lax.ppermute(s_rot, ax, perm))
            return jax.lax.ppermute(x_rot, ax, perm)

        def body(k, carry):
            acc, x_rot = carry
            bs = jax.lax.dynamic_index_in_dim(src_l, k, 0, keepdims=False)
            bp = jax.lax.dynamic_index_in_dim(indptr, k, 0, keepdims=False)
            xk = block(k, x_rot)
            if coeff is None:
                part = kops.spmm_csr("sum", xk, bp, bs, n_local, gather=True,
                                     impl=route)
            else:
                bc = jax.lax.dynamic_index_in_dim(coeff, k, 0, keepdims=False)
                part = kops.spmm_csr("sum", xk[bs] * bc[:, None], bp, bs,
                                     n_local, impl=route)
            # rotate: after this permute device i holds block (i+k+1)%p
            return acc + part, rotate(x_rot)

        if quantize:
            scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
            q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
            payload = (q, scale)
        else:
            payload = x
        acc = jnp.zeros((n_local, x.shape[-1]), x.dtype)
        acc, _ = jax.lax.fori_loop(0, steps, body, (acc, payload))
        return acc

    xspec = P(axes if len(axes) > 1 else axes[0], None)
    bspec = P(axes if len(axes) > 1 else axes[0], None, None)
    in_specs = (xspec, bspec, bspec) + ((bspec,) if with_coeff else ())
    # the Pallas kernel's output type names no mesh axes, so the map does
    # not check how values vary over them
    return jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=xspec, check_vma=False)
