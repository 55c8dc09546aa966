"""TieredExecutor — makes a placement Plan *functional* on every backend.

The pre-redesign ``apply_placements`` was advisory: on backends without
a ``pinned_host`` memory kind (CPU CI) a host demotion changed nothing
but a ``describe()`` string.  The executor gives every backend a real
slow tier:

  * **memory-kind path** (backends that list the tier's memory kind,
    e.g. ``pinned_host`` on TPU): a demoted leaf is ``device_put`` onto
    its tier's JAX ``memory_kind`` and the jitted step takes it there
    as an argument.  The step moves it into device memory itself
    (``on_device``) and writes updated leaves back to their tier
    (``like_placement``), so the host link carries exactly the traffic
    the cost model prices.  ``fetch`` is a no-op here and ``commit``
    only re-asserts the placement.
  * **host-store path** (everything else): a demoted leaf's bytes are
    committed to host memory as a numpy buffer — it genuinely leaves
    the device buffer pool.  Each step the executor *fetches* demoted
    leaves back onto the device (``jax.device_put`` dispatches the H2D
    copy asynchronously, overlapping the previous step's tail),
    computes, then *commits* the updated bytes back to the host store.
    The executor retains no reference to the device copies — once a
    step's state is committed the only live device buffers are the ones
    the next fetch creates, so demoted bytes genuinely leave the device
    pool between steps.  One fetch serves every microbatch of the step
    — the tables don't change inside one accumulated batch — so the
    stream runs at step granularity upward and microbatch granularity
    during serving gathers.

Both paths round-trip bytes exactly (device↔host copies of the same
float32 buffers), so a demoted run computes *bit-identical* results to
the all-fast run — on the ``uniform`` topology the cost model prices
that demotion at exactly 0.0 and CPU CI pins the bit-identity
(tests/test_memory.py).

With ``embed_store='int8'`` (``repro.api.CompressionCfg``) demoted
host-store embedding tables are held *quantized*: the store keeps
per-row symmetric int8 values plus one fp32 scale per row (~1/4 the
bytes — the capacity multiplier the planner prices via
``AccessProfile.store_bytes``), and every fetch dequantizes on the way
up.  The state's own leaf is the dequantized fp32 view, so checkpoints,
eval snapshots and the jitted step see ordinary float32 arrays whose
values have round-tripped through int8 (max abs error <= the row's
quantization scale — pinned by tests/test_compression.py).

``HostResident`` is the row-granular serving facade: a slow-tier
embedding table whose bytes live in the host store and whose rows are
gathered/streamed on demand (``take``/``block``), so a query batch
moves O(batch × D) bytes instead of the whole table —
``eval.topk.streaming_topk`` consumes it directly.
``QuantizedHostResident`` is its int8 arm: rows live as (q, scale) and
dequantize on gather.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.memory.policies import Plan
from repro.optim.compression import dequantize_rows_int8, quantize_rows_int8


def memory_kind_sharding(kind: str | None):
    """A single-device sharding onto the given memory kind, when the
    backend lists one; None otherwise (then the host-store path takes
    over).  Any other failure propagates: a placement that cannot be
    made must not fall back to another tier in silence."""
    if kind is None:
        return None
    dev = jax.devices()[0]
    if kind not in {m.kind for m in dev.addressable_memories()}:
        return None
    return jax.sharding.SingleDeviceSharding(dev, memory_kind=kind)


def _in_host_memory(x) -> bool:
    space = getattr(jax.typeof(x), "memory_space", None)
    return space == jax.memory.Space.Host


def on_device(tree):
    """Inside a jitted step: leaves living in host memory (the
    memory-kind slow tier) -> device memory, in the program's own H2D
    stream; every other leaf passes through."""
    return jax.tree.map(
        lambda x: jax.device_put(x, jax.memory.Space.Device)
        if _in_host_memory(x) else x, tree)


def like_placement(new, old):
    """Inside a jitted step: each leaf of ``new`` goes back to host
    memory where the matching leaf of ``old`` came from it."""
    return jax.tree.map(
        lambda n, o: jax.device_put(n, jax.memory.Space.Host)
        if _in_host_memory(o) else n, new, old)


class HostResident:
    """A slow-tier table: bytes live in host memory, rows stream to the
    device on demand.  Shape/dtype/nbytes mirror the array so facades
    (Recommender) can treat it like one."""

    def __init__(self, arr):
        self.arr = np.asarray(arr)

    shape = property(lambda self: self.arr.shape)
    dtype = property(lambda self: self.arr.dtype)
    nbytes = property(lambda self: self.arr.nbytes)

    def take(self, ids) -> np.ndarray:
        """Row-granular gather: only the requested rows leave the host
        store (O(len(ids) × D) bytes)."""
        return self.arr[np.asarray(ids)]

    def block(self, ids) -> np.ndarray:
        """Contiguous-ish block stream for the scorer's item blocks
        (same gather semantics as ``take``; kept separate for intent)."""
        return self.arr[np.asarray(ids)]


class QuantizedHostResident(HostResident):
    """An int8-stored slow-tier table: the host store holds per-row
    symmetric int8 values plus one fp32 scale per row (~1/4 the dense
    bytes) and every gather dequantizes on the way to the device."""

    def __init__(self, arr):
        arr = np.asarray(arr, np.float32)
        self.q, self.scale = quantize_rows_int8(arr)
        self._shape = arr.shape

    shape = property(lambda self: self._shape)
    dtype = property(lambda self: np.dtype(np.float32))
    nbytes = property(lambda self: self.q.nbytes + self.scale.nbytes)

    def dense(self) -> np.ndarray:
        """The full dequantized fp32 view (checkpoint/debug path)."""
        return dequantize_rows_int8(self.q, self.scale)

    def take(self, ids) -> np.ndarray:
        ids = np.asarray(ids)
        return dequantize_rows_int8(self.q[ids], self.scale[ids])

    def block(self, ids) -> np.ndarray:
        return self.take(ids)


class TieredExecutor:
    """Drives one Plan's placements on the current backend."""

    def __init__(self, plan: Plan, prefixes: tuple[str, ...] = ("params",
                                                                "opt"),
                 embed_store: str = "fp32", cache_rows: int = 0):
        if embed_store not in ("fp32", "int8"):
            raise ValueError(f"unknown embed_store {embed_store!r}; "
                             "known: fp32, int8")
        if cache_rows < 0:
            raise ValueError(f"cache_rows must be >= 0, got {cache_rows}")
        self.plan = plan
        self.topology = plan.topology
        self.prefixes = prefixes
        self.embed_store = embed_store
        self.cache_rows = int(cache_rows)
        # hot-row caches wrapped around host-store serving tables
        self.caches: dict[str, object] = {}
        # host-store leaves currently demoted (by profile name)
        self._host_names: set[str] = set()
        # memory-kind leaves currently demoted: name -> their sharding
        self._kind_sharding: dict[str, object] = {}
        # int8 buffers for quantized host-store tables: name -> (q, scale)
        self._int8: dict[str, tuple[np.ndarray, np.ndarray]] = {}

    # ------------------------------------------------------------ queries
    def _demoted_tier(self, name: str):
        pl = self.plan.placements.get(name)
        if pl is None or pl.tier == self.topology.fast.name:
            return None
        return self.topology.tier(pl.tier)

    @property
    def has_demotions(self) -> bool:
        return any(not self.plan.is_fast(n) for n in self.plan.placements)

    def _walk(self, state, leaf_fn):
        out = {}
        for prefix in self.prefixes:
            tree = state[prefix]
            flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
            leaves = [leaf_fn(prefix + jax.tree_util.keystr(kp), leaf)
                      for kp, leaf in flat]
            out[prefix] = jax.tree_util.tree_unflatten(treedef, leaves)
        for k in state:
            if k not in out:
                out[k] = state[k]
        return out

    def _wants_int8(self, name: str, leaf) -> bool:
        """Embedding tables demoted to the host store are the quantized
        arm: 2-D float32 ``params`` leaves (tables), when the executor
        runs with ``embed_store='int8'``."""
        return (self.embed_store == "int8"
                and name.startswith("params")
                and getattr(leaf, "ndim", 0) == 2
                and getattr(leaf, "dtype", None) == np.float32)

    def _store(self, name: str, leaf):
        """Commit one host-store leaf: quantized tables keep (int8,
        scale) buffers and the state carries the dequantized fp32 view;
        everything else stores dense fp32 bytes."""
        if self._wants_int8(name, leaf):
            q, scale = quantize_rows_int8(np.asarray(leaf))
            self._int8[name] = (q, scale)
            return dequantize_rows_int8(q, scale)
        self._int8.pop(name, None)
        return np.asarray(leaf)

    # ------------------------------------------------------------ placement
    def place(self, state) -> tuple[object, int]:
        """Move every demoted state leaf onto its planned tier: the
        tier's memory kind when the backend has it, the host store
        otherwise.  Returns (state, n_offloaded)."""
        self._host_names.clear()
        self._kind_sharding.clear()
        self._int8.clear()
        moved = 0

        fast = memory_kind_sharding(self.topology.fast.memory_kind)

        def place_leaf(name, leaf):
            nonlocal moved
            tier = self._demoted_tier(name)
            if tier is None:
                # committed like the demoted leaves, so the first step
                # sees the same argument placements as every later one
                # (a mix of committed and uncommitted inputs recompiles)
                return leaf if fast is None else jax.device_put(leaf, fast)
            sh = memory_kind_sharding(tier.memory_kind)
            moved += 1
            if sh is not None:
                self._kind_sharding[name] = sh
                return jax.device_put(leaf, sh)
            self._host_names.add(name)
            return self._store(name, leaf)

        out = self._walk(state, place_leaf)
        return out, moved

    # ------------------------------------------------------------ streaming
    def fetch(self, state):
        """Demoted host-store leaves -> device (async H2D dispatch; the
        returned state is the only reference holder, so the previous
        step's copies free as soon as its state is dropped).  Identity
        when nothing is in the host store (memory-kind path, or no
        demotions)."""
        if not self._host_names:
            return state
        return self._walk(
            state, lambda name, leaf:
            jax.device_put(leaf) if name in self._host_names else leaf)

    def commit(self, state):
        """Write demoted leaves' updated bytes back to the host store
        (the slow tier owns them between steps; quantized tables
        re-quantize here, so the carried state is always the int8
        round-trip).  Memory-kind leaves are re-placed on their tier — a
        no-op when the step already wrote them there.  Identity when
        nothing is demoted."""
        if not self._host_names and not self._kind_sharding:
            return state

        def commit_leaf(name, leaf):
            if name in self._host_names:
                return self._store(name, leaf)
            sh = self._kind_sharding.get(name)
            return leaf if sh is None else jax.device_put(leaf, sh)

        return self._walk(state, commit_leaf)

    # ------------------------------------------------------------ serving
    def host_table(self, name: str, table):
        """Wrap a demoted table in the row-granular serving facade when
        it belongs to the host store (the int8 dequant-on-gather facade
        under ``embed_store='int8'``); device_put it when its tier has a
        real memory kind; pass through otherwise.  With ``cache_rows``
        set, the host-store facade gains a device-resident
        ``HotRowCache`` front (LFU hot set; fills ride the same async
        H2D dispatch as ``fetch``)."""
        tier = self._demoted_tier(name)
        if tier is None:
            return table
        sh = memory_kind_sharding(tier.memory_kind)
        if sh is not None:
            return jax.device_put(table, sh)
        if self.embed_store == "int8" and getattr(table, "ndim", 0) == 2:
            facade = QuantizedHostResident(table)
        else:
            facade = HostResident(table)
        if self.cache_rows > 0:
            from repro.memory.cache import HotRowCache
            facade = HotRowCache(facade, self.cache_rows)
            self.caches[name] = facade
        return facade

    def prefetch_rows(self, name: str, ids) -> None:
        """Warm a serving table's hot-row cache with the given row ids
        (no-op for uncached tables)."""
        cache = self.caches.get(name)
        if cache is not None:
            cache.prefill(ids)

    def cache_stats(self) -> dict[str, dict]:
        """Per-table hit/miss/bytes-streamed counters for the serving
        caches this executor handed out."""
        return {name: c.stats.to_dict() for name, c in self.caches.items()}

    def store_nbytes(self, name: str) -> int | None:
        """Actual host-store bytes of a quantized table (q + scales), or
        None when the leaf isn't int8-resident — what the planner's
        ``store_bytes`` pricing should match."""
        if name not in self._int8:
            return None
        q, scale = self._int8[name]
        return q.nbytes + scale.nbytes

    def describe(self) -> str:
        demoted = self.plan.demoted()
        mode = "memory-kind" if not self._host_names and demoted \
            else "host-store"
        store = f" embed_store=int8({len(self._int8)})" \
            if self.embed_store == "int8" else ""
        cache = ""
        if self.caches:
            parts = []
            for name, c in self.caches.items():
                s = c.stats
                parts.append(f"{name}: rows={c.rows} "
                             f"hit_rate={s.hit_rate:.2f} "
                             f"streamed={s.bytes_streamed}B")
            cache = f" cache[{'; '.join(parts)}]"
        return (f"TieredExecutor[{self.topology.name}] "
                f"demoted={len(demoted)} ({mode}){store}{cache}")
