"""Operations and compulsory bytes of one LightGCN training step,
computed from the configuration's shapes alone.

One step is one full-graph forward and backward over all ``E`` edges
with ``L`` propagation layers, BPR on ``B`` (user, positive, negative)
rows, and one Adam update of both embedding tables.  Recomputed work
does not count; padding and masking in the program do not count.
"""
from __future__ import annotations

F32 = 4          # bytes of a float32 element
IDX = 4          # bytes of an int32 index


def spmm_call_bytes(n_src: int, n_dst: int, n_edges: int, d: int) -> int:
    """Compulsory HBM bytes of one gather-SpMM call ``out = A x``: the
    source table, the edge-index array and ``indptr`` read once, the
    output table written once.  Every implementation of the aggregation
    moves at least this much, whatever its route."""
    return (n_src * d * F32 + n_edges * IDX + (n_dst + 1) * IDX
            + n_dst * d * F32)


def spmm_edge_row_bytes(n_edges: int, d: int) -> int:
    """Bytes one call moves when it reads one source row per edge (the
    per-edge row gather of the Pallas kernel), for context."""
    return n_edges * d * F32


def spmm_step_bytes(n_users: int, n_items: int, n_edges: int, d: int,
                    n_layers: int) -> int:
    """Compulsory bytes of the 4L SpMM calls of one step: per layer a
    ``u2i`` and an ``i2u`` call forward, and their transposes (an
    ``i2u``- and a ``u2i``-type call) backward."""
    u2i = spmm_call_bytes(n_users, n_items, n_edges, d)
    i2u = spmm_call_bytes(n_items, n_users, n_edges, d)
    return 2 * n_layers * (u2i + i2u)


def step_flops(n_users: int, n_items: int, n_edges: int, d: int,
               n_layers: int, batch: int) -> int:
    """Model FLOPs of one step (a multiply-add counts as two):

    - aggregation: 4L SpMM passes (two directions, forward and
      backward) of ``2 E d`` each;
    - node-level work per layer and direction of the pass: the two
      degree scalings and the layer sum forward (``3 N d``), the same
      backward, plus the layer mean forward and backward (``2 N d``);
    - BPR: two dot products and three squared norms of ``d``-vectors per
      row forward (``10 B d``), twice that backward;
    - Adam: 14 elementwise operations per parameter.
    """
    n = n_users + n_items
    aggregation = 4 * n_layers * 2 * n_edges * d
    node = (6 * n_layers + 2) * n * d
    bpr = 30 * batch * d
    adam = 14 * n * d
    return aggregation + node + bpr + adam
