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
the output block never leaves the device.  The ring always visits all
P owners with the fp32 block, so it applies every edge exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.kernels import ops as kops


def bucket_edges(src: np.ndarray, dst: np.ndarray, n_nodes: int, p: int,
                 pad_multiple: int = 8):
    """Bucket edges by (dst device, relative ring step) into CSR buckets.

    Nodes are block-partitioned: device d owns rows [d*n_local,
    (d+1)*n_local).  Bucket [d, k] holds the edges whose dst lives on d
    and whose src lives on (d+k) mod p, sorted by *local* dst row, with
    local source rows, padded to a uniform length.  ``indptr[d, k]``
    holds the bucket's row pointers: the edges of local row r are
    slots ``indptr[d, k, r]:indptr[d, k, r + 1]``, and slots past
    ``indptr[d, k, -1]`` are padding (source row 0).

    ``n_nodes`` must be a multiple of ``p``; ragged graphs are padded to
    the next multiple by the shard layer (``pipeline.shard``) before
    reaching here — padded rows simply own no edges.

    One stable sort over a combined (bucket, local dst row) key groups
    and orders every edge; edges of one row keep their original order.

    Returns (src_l [p, p, E_b], indptr [p, p, n_local + 1], n_local).
    """
    if n_nodes % p:
        raise ValueError(f"n_nodes {n_nodes} not divisible by {p} devices; "
                         "pad via pipeline.shard.NodePartition")
    n_local = n_nodes // p
    src = np.asarray(src)
    dst = np.asarray(dst)
    ddev = dst // n_local
    rel = (src // n_local - ddev) % p
    n_rows = p * p * n_local                   # (bucket, local row) keys
    key = (ddev * p + rel) * n_local + dst % n_local
    order = np.argsort(key, kind="stable")
    per_row = np.bincount(key, minlength=n_rows).reshape(p * p, n_local)
    counts = per_row.sum(1)
    emax = max(int(counts.max()) if counts.size else 1, 1)
    emax = int(np.ceil(emax / pad_multiple)) * pad_multiple
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    within = np.arange(len(order)) - np.repeat(starts, counts)
    slot = key[order] // n_local * emax + within   # place in the padded cube
    src_l = np.zeros((p, p, emax), np.int32)
    src_l.reshape(-1)[slot] = src[order] % n_local
    indptr = np.zeros((p * p, n_local + 1), np.int32)
    np.cumsum(per_row, axis=1, out=indptr[:, 1:])
    return src_l, indptr.reshape(p, p, n_local + 1), n_local


def make_ring_spmm(mesh, axis, n_local: int, impl: str = "xla"):
    """Build ring_spmm(x, src_l, indptr) -> A @ x over the flattened
    device ring of ``axis`` (one name or a tuple of names).

    x: [N, D] row-sharded on ``axis``; bucket arrays [P, P, ...] sharded
    on their leading (dst-device) dim, as produced by ``bucket_edges``.

    Each of the P ring steps aggregates its bucket with one gather-SpMM
    call (``kernels.ops.spmm_csr``, dispatched by ``impl``: the Pallas
    DMA-ring kernel on TPU, the XLA oracle elsewhere) into the carried
    [n_local, D] accumulator.  The route is counted at trace time
    (``repro.obs`` ``ring_route``).
    """
    axes = axis if isinstance(axis, (tuple, list)) else (axis,)
    axes = tuple(axes)
    p = int(np.prod([mesh.shape[a] for a in axes]))

    def local_fn(x, src_l, indptr):
        # shard_map blocks: x [n_local, D]; buckets [1, P, ...]
        obs.count("ring_route", impl)
        src_l = src_l[0]
        indptr = indptr[0]
        perm = [(j, (j - 1) % p) for j in range(p)]
        ax = axes if len(axes) > 1 else axes[0]

        def body(k, carry):
            acc, x_rot = carry
            bs = jax.lax.dynamic_index_in_dim(src_l, k, 0, keepdims=False)
            bp = jax.lax.dynamic_index_in_dim(indptr, k, 0, keepdims=False)
            part = kops.spmm_csr("sum", x_rot, bp, bs, n_local, gather=True,
                                 impl=impl)
            # rotate: after this permute device i holds block (i+k+1)%p
            return acc + part, jax.lax.ppermute(x_rot, ax, perm)

        acc = jnp.zeros((n_local, x.shape[-1]), x.dtype)
        acc, _ = jax.lax.fori_loop(0, p, body, (acc, x))
        return acc

    xspec = P(axes if len(axes) > 1 else axes[0], None)
    bspec = P(axes if len(axes) > 1 else axes[0], None, None)
    # the Pallas kernel's output type names no mesh axes, so the map does
    # not check how values vary over them
    return jax.shard_map(local_fn, mesh=mesh, in_specs=(xspec, bspec, bspec),
                         out_specs=xspec, check_vma=False)
