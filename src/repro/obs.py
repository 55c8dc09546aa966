"""The program's own observability: host spans, device scopes and a
compile counter.

Nothing here records time.  Spans are ``jax.profiler.TraceAnnotation``s
on the host and scopes are ``jax.named_scope``s inside traced programs,
so both land in a profiler trace on the profiler's clock, beside the
device planes; with no trace running they cost a few microseconds (a
span) or nothing (a scope: it only names the ops of the compiled
program in their metadata).

Host spans, each carrying the step number as a ``step`` stat:

  ``train.step``       one engine step (``Pipeline.step_fn``), the
                       parent of the two below
  ``train.batch``      drawing the step's target batch: the loader's
                       microbatches and the negatives (host work the
                       device waits for unless it overlaps)
  ``train.loss_sync``  the host blocking on the device for the step's
                       loss

Device scope: every aggregation over the graph (``pipeline/sparse.py``)
runs under ``agg/<kind>``, ``kind`` one of ``AGG_KINDS``, in its
forward and in its custom-VJP backward, so an op's name stack (the
``tf_op`` of its trace event, the ``op_name`` of its HLO metadata)
holds ``agg`` as one component whatever transform wraps it.  A
model's node-level work on a layer's aggregate (NGCF's and GCN's
weights and activations, ``pipeline/registry.py``) runs under
``transform`` the same way.

Counters: one ``jax.monitoring`` listener counts, by function name,
the lowerings of jitted functions (one per top-level cache miss, whether
or not the persistent compilation cache then hits) and the backend
compiles.  Trace-time counts join them through ``count``:
``spmm_inflight``, keyed by DMA ring depth, counts the traces of the
Pallas SpMM (``kernels/spmm.py``), one per call shape a program lowers;
``ngcf_route``, keyed by the Hadamard route the graph resolved
(``pipeline/sparse.py``), counts the traces of the NGCF forward;
``ring_route``, keyed by the route a ring SpMM aggregates its buckets
by (``pallas`` or ``xla``), counts the traces of the ring's per-device
function (``dist/ring_spmm.py``).  ``counters()`` returns a snapshot.
"""
from __future__ import annotations

import collections
import contextlib
import threading

import jax
from jax import monitoring

STEP_SPAN = "train.step"
BATCH_SPAN = "train.batch"
LOSS_SYNC_SPAN = "train.loss_sync"
AGG_SCOPE = "agg"
AGG_KINDS = ("u2i", "i2u", "sym", "edge", "hadamard")
TRANSFORM_SCOPE = "transform"

# jax.monitoring duration events -> counter name
_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
    "/jax/core/compile/backend_compile_duration": "compiles",
}
# counted by the program at trace time (``count``)
_TRACE_COUNTS = ("spmm_inflight", "ngcf_route", "ring_route")


def span(name: str, step: int):
    """A host span ``name`` carrying ``step`` as a stat."""
    return jax.profiler.TraceAnnotation(name, step=step)


@contextlib.contextmanager
def agg_scope(kind: str):
    """Name the ops traced inside ``agg/<kind>``."""
    if kind not in AGG_KINDS:
        raise ValueError(f"aggregation kind {kind!r} not in {AGG_KINDS}")
    with jax.named_scope(AGG_SCOPE), jax.named_scope(kind):
        yield


def transform_scope():
    """Name the ops traced inside ``transform``."""
    return jax.named_scope(TRANSFORM_SCOPE)


class _Counter:
    """Counts of the ``_EVENTS`` by function name and of the
    ``_TRACE_COUNTS`` by key, safe across threads (JAX may lower and
    compile off the main thread)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = {k: collections.Counter()
                        for k in (*_EVENTS.values(), *_TRACE_COUNTS)}

    def __call__(self, event: str, duration: float, **kw) -> None:
        kind = _EVENTS.get(event)
        if kind is not None:
            self.add(kind, kw.get("fun_name", ""))

    def add(self, kind: str, key) -> None:
        with self._lock:
            self._counts[kind][key] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {k: dict(c) for k, c in self._counts.items()}


_COUNTER = _Counter()
monitoring.register_event_duration_secs_listener(_COUNTER)


def count(kind: str, key) -> None:
    """One more ``key`` under the trace-time counter ``kind``."""
    if kind not in _TRACE_COUNTS:
        raise ValueError(f"counter {kind!r} not in {_TRACE_COUNTS}")
    _COUNTER.add(kind, key)


def counters() -> dict:
    """``{"lowerings": {fun_name: n}, "compiles": {fun_name: n},
    "spmm_inflight": {depth: n}, "ngcf_route": {route: n},
    "ring_route": {route: n}}`` since this module was first imported."""
    return _COUNTER.snapshot()
