"""ShardPlan — the mesh-parallel execution contract of one training run.

The paper's headline comparison (§7, Table 6) pits single-machine
full-graph training against distributed subgraph training; this module
is what lets the full-graph side *scale out* without changing its math.
One ``ShardPlan`` describes the whole sharded execution and flows
through every layer:

  * ``pipeline.sparse``   — routes model aggregation through the ring
    SpMM (``dist.ring_spmm``) when ``wants_ring``: features row-sharded
    over the device ring, edges bucketed by (dst device, ring distance),
    the NUMA-blocked Fig 11 schedule as collective-permutes — always the
    full ring of P steps, so every edge is applied exactly;
  * ``pipeline.plan``     — profiles *per-device* tensor shards and runs
    the tiered-memory knapsack against the per-device HBM budget; the
    derived microbatch is the per-shard microbatch (global batch =
    ``n_shards x microbatch x accum``);
  * ``pipeline.engine`` / ``runtime.loop`` — the accumulation step runs
    under ``dist.hints.sharding_hints`` with the batch sharded over the
    data-parallel axes and gradients combined by GSPMD all-reduce
    (psum);
  * ``repro.api``         — ``MeshCfg`` on the ExperimentSpec is the
    declarative surface that builds one of these;
  * ``eval.topk``         — streaming top-K shards its user batches over
    the same axes.

Node partitioning follows GNNear's partition-the-aggregation design:
each device owns a contiguous block of the *unified* node space (users
then items), with the node count padded up to the next multiple of the
shard count — padded rows have no edges, so they aggregate to zero and
are sliced off (see ``NodePartition``).  The single-device plan
(``shape=(1,)``, no explicit spmm) is inert: every helper degenerates
to the identity and the unsharded pipeline path is taken bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def auto_axes(shape) -> tuple[str, ...]:
    """Default axis names for a mesh shape: the shard layer treats every
    axis as data-parallel (model parallelism is out of scope here), so
    the names only need to be unique and recognizable."""
    n = len(tuple(shape))
    if n == 1:
        return ("data",)
    if n == 2:
        return ("pod", "data")
    return tuple(f"data{i}" for i in range(n))


@functools.lru_cache(maxsize=None)
def _mesh_for(shape: tuple[int, ...], axes: tuple[str, ...]):
    """One mesh per (shape, axes) per process — meshes are cheap but
    building them repeatedly defeats jit caching of shard_mapped fns.
    Axes are ``Auto``: the shard layer places inputs and lets GSPMD
    propagate shardings through the step (``dist.hints``)."""
    n_dev = len(jax.devices())
    need = int(np.prod(shape))
    if need > n_dev:
        raise ValueError(
            f"mesh shape {shape} needs {need} devices but only {n_dev} "
            f"are visible; on CPU CI export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


@dataclasses.dataclass(frozen=True)
class NodePartition:
    """Block partition of ``n_nodes`` rows over ``n_shards`` devices,
    padded so every device owns the same number of rows.  Padded rows
    carry no edges, so sharded aggregation leaves them zero and callers
    slice them off (``trim``)."""
    n_nodes: int
    n_shards: int

    @property
    def n_pad(self) -> int:
        """Node count rounded up to the next multiple of the shard
        count (the satellite fix for ``bucket_edges``'s hard
        divisibility requirement)."""
        return math.ceil(self.n_nodes / self.n_shards) * self.n_shards

    @property
    def n_local(self) -> int:
        return self.n_pad // self.n_shards

    def pad_rows(self, x):
        """[n_nodes, D] -> [n_pad, D], zero rows appended."""
        import jax.numpy as jnp
        extra = self.n_pad - self.n_nodes
        if extra == 0:
            return x
        return jnp.pad(x, ((0, extra), (0, 0)))

    def trim(self, x):
        """[n_pad, D] -> [n_nodes, D]: mask the padded rows back out of
        the aggregation result."""
        return x if self.n_pad == self.n_nodes else x[:self.n_nodes]


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Mesh shape/axes + SpMM dispatch for one run.

    ``spmm``: ``None`` = auto (ring when the mesh has >1 device, the
    plain kernel dispatch otherwise); ``"ring"`` forces the ring path
    even on a 1-device mesh (degenerate ring — useful for testing the
    dispatch without multiple devices).
    """
    shape: tuple[int, ...] = (1,)
    axes: tuple[str, ...] = ("data",)
    spmm: str | None = None          # None (auto) | 'ring'

    def __post_init__(self):
        object.__setattr__(self, "shape", tuple(int(s) for s in self.shape))
        object.__setattr__(self, "axes", tuple(str(a) for a in self.axes))
        if len(self.shape) != len(self.axes):
            raise ValueError(f"mesh shape {self.shape} has "
                             f"{len(self.shape)} dims but axes {self.axes} "
                             f"name {len(self.axes)}")
        if self.spmm not in (None, "ring"):
            raise ValueError(f"unknown spmm dispatch {self.spmm!r}; "
                             "known: None (auto), 'ring'")

    # ------------------------------------------------------------ shape
    @property
    def n_shards(self) -> int:
        return int(np.prod(self.shape))

    @property
    def is_sharded(self) -> bool:
        return self.n_shards > 1

    @property
    def wants_ring(self) -> bool:
        """Route aggregation through ``dist.ring_spmm``?"""
        return self.spmm == "ring" or (self.spmm is None and self.is_sharded)

    @property
    def dp(self):
        """The data-parallel axis argument (one name or a tuple) for
        ``make_ring_spmm`` / ``sharding_hints``."""
        return self.axes if len(self.axes) > 1 else self.axes[0]

    # ------------------------------------------------------------ mesh
    def build_mesh(self):
        return _mesh_for(self.shape, self.axes)

    def partition(self, n_nodes: int) -> NodePartition:
        return NodePartition(int(n_nodes), self.n_shards)

    # ------------------------------------------------------------ placement
    def batch_sharding(self, ndim: int = 1) -> NamedSharding:
        """Leading dim sharded over every mesh axis, the rest replicated
        — the per-shard view of a (users, pos, neg) batch chunk."""
        spec = P(self.dp, *([None] * (ndim - 1)))
        return NamedSharding(self.build_mesh(), spec)

    def shard_batch(self, *arrays):
        """device_put each array with its leading dim sharded over the
        mesh.  Arrays whose leading dim does not divide the shard count
        are left unsharded (replicated by jit) — the engine only feeds
        divisible chunks on the hot path."""
        out = []
        p = self.n_shards
        for a in arrays:
            if a.shape[0] % p == 0:
                a = jax.device_put(a, self.batch_sharding(a.ndim))
            out.append(a)
        return tuple(out) if len(out) > 1 else out[0]

    def _leaf_sharding(self, leaf) -> NamedSharding:
        """Row-shard embedding-table-like leaves (>=2 dims, leading dim
        divisible by the shard count); replicate everything else.  This
        is the storage analogue of the per-worker memory budget framing
        (MTrainS): each shard holds 1/P of every large table."""
        mesh = self.build_mesh()
        if getattr(leaf, "ndim", 0) >= 2 and leaf.shape[0] % self.n_shards == 0:
            return NamedSharding(mesh, P(self.dp, *([None] * (leaf.ndim - 1))))
        return NamedSharding(mesh, P())

    def shard_state(self, tree):
        """Place every state leaf onto the mesh: large tables row-sharded,
        small leaves replicated.  Identity on a 1-device mesh."""
        if not self.is_sharded:
            return tree
        return jax.tree.map(
            lambda leaf: jax.device_put(leaf, self._leaf_sharding(leaf)),
            tree)

    def shard_divisor(self, leaf_shape) -> int:
        """How many ways a tensor of this shape is split per device —
        the planner divides its nbytes by this (per-device profiling)."""
        if not self.is_sharded:
            return 1
        if len(leaf_shape) >= 2 and leaf_shape[0] % self.n_shards == 0:
            return self.n_shards
        return 1

    def describe(self) -> str:
        return (f"mesh={'x'.join(map(str, self.shape))} "
                f"axes={','.join(self.axes)} "
                f"spmm={'ring' if self.wants_ring else 'kernel'}")

    # ------------------------------------------------------------ builders
    @classmethod
    def from_config(cls, mesh_shape=(1,), mesh_axes=None,
                    spmm=None) -> "ShardPlan | None":
        """The engine-facing constructor: returns ``None`` for the inert
        single-device default (no mesh, bit-identical legacy path), a
        live plan otherwise."""
        shape = tuple(int(s) for s in mesh_shape)
        axes = tuple(mesh_axes) if mesh_axes else auto_axes(shape)
        plan = cls(shape, axes, spmm)
        if not plan.is_sharded and not plan.wants_ring:
            return None
        return plan


def parse_mesh(text: str) -> tuple[int, ...]:
    """'4' -> (4,); '2x2' -> (2, 2) — the --mesh CLI syntax."""
    try:
        return tuple(int(t) for t in text.lower().split("x"))
    except ValueError:
        raise ValueError(f"bad mesh spec {text!r}; expected e.g. '4' or "
                         "'2x2'") from None
