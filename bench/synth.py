"""The benchmark's graph generator: a verbatim copy of the program's
``repro.data.synth.generate_bipartite`` (Zipf 1.05 user activity and
item popularity, dedup, id shuffle), kept here so that a later change
to the program's generator cannot move the yardstick.

A cell's graph structure comes from the configuration's ``graph_seed``;
the run's ``--seed`` relabels it (``relabel``).  The same seed gives the
same graph.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np


@dataclasses.dataclass
class Graph:
    user: np.ndarray   # int32[E]
    item: np.ndarray   # int32[E]
    n_users: int
    n_items: int

    @property
    def n_edges(self) -> int:
        return len(self.user)


def zipf_probs(n: int, alpha: float = 1.05) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** alpha
    return p / p.sum()


def generate_bipartite(n_users: int, n_items: int, n_edges: int,
                       seed: int = 0, alpha: float = 1.05) -> Graph:
    """Power-law bipartite generator: user activity and item popularity
    both Zipf-distributed (matches the paper's Fig 13 degree shape).
    Deduplicates; may return slightly fewer than n_edges."""
    rng = np.random.default_rng(seed)
    pu = zipf_probs(n_users, alpha)
    pi = zipf_probs(n_items, alpha)
    # sample-dedup-resample until filled (Zipf heads collide heavily)
    keys: np.ndarray = np.zeros(0, np.int64)
    for _ in range(12):
        need = n_edges - len(keys)
        if need <= 0:
            break
        m = int(need * 1.5) + 16
        u = rng.choice(n_users, m, p=pu)
        i = rng.choice(n_items, m, p=pi)
        keys = np.unique(np.concatenate([keys, u.astype(np.int64) * n_items + i]))
    if len(keys) > n_edges:
        keys = rng.choice(keys, n_edges, replace=False)
    u = (keys // n_items).astype(np.int32)
    i = (keys % n_items).astype(np.int32)
    # shuffle user/item id space so ids are not popularity-ordered
    uperm = rng.permutation(n_users).astype(np.int32)
    iperm = rng.permutation(n_items).astype(np.int32)
    return Graph(uperm[u], iperm[i], n_users, n_items)


def base_graph(cfg: dict, cache_dir: str | None = None) -> Graph:
    """The configuration's graph structure: ``generate_bipartite`` at
    the configuration's own ``graph_seed``, the same for every run.
    With ``cache_dir`` it is generated once per checkout and loaded
    afterwards."""
    args = (cfg["n_users"], cfg["n_items"], cfg["n_edges"],
            cfg["graph_seed"], cfg["zipf_alpha"])
    path = None
    if cache_dir is not None:
        name = "graph-" + "-".join(str(a) for a in args) + ".npz"
        path = os.path.join(cache_dir, name)
        if os.path.exists(path):
            with np.load(path) as z:
                return Graph(z["user"], z["item"], cfg["n_users"],
                             cfg["n_items"])
    g = generate_bipartite(*args[:3], seed=args[3], alpha=args[4])
    if path is not None:
        os.makedirs(cache_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp.npz"
        np.savez(tmp, user=g.user, item=g.item)
        os.replace(tmp, path)
    return g


def _block_perm(rng, n: int, offset: int, block: int) -> np.ndarray:
    """A permutation of ids ``[0, n)``, placed at ``offset`` in the node
    space, that moves no id out of its block of ``block`` nodes."""
    cuts = [0] + [b * block - offset
                  for b in range(offset // block + 1, (offset + n) // block + 1)
                  if 0 < b * block - offset < n] + [n]
    return np.concatenate([lo + rng.permutation(hi - lo)
                           for lo, hi in zip(cuts, cuts[1:])])


def relabel(g: Graph, seed: int, n_blocks: int) -> Graph:
    """The run's graph: ``g`` with its user and item ids permuted by
    ``seed``, each within its block when the node space (users, then
    items) is cut into ``n_blocks`` equal blocks.  Every seed thus gives
    the same degrees and the same edge count between any two blocks, so
    the work and the shapes of a cell do not depend on the seed; only
    which ids carry them does."""
    rng = np.random.default_rng(seed)
    block = -(-(g.n_users + g.n_items) // n_blocks)
    uperm = _block_perm(rng, g.n_users, 0, block).astype(np.int32)
    iperm = _block_perm(rng, g.n_items, g.n_users, block).astype(np.int32)
    return Graph(uperm[g.user], iperm[g.item], g.n_users, g.n_items)
