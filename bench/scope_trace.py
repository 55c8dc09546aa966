"""Per-layer numbers read from the program's own spans and scopes
(``repro.obs``) in the profiler trace of the measured window.

Per chip: device seconds of the (non-container) operations whose name
stack holds the aggregation scope ``agg``, with and without collective
ops, and the chip's idle seconds that lie inside ``train.batch`` host
spans; besides, the host seconds inside those spans.  Window, steps and
idle intervals are the harness's own (``bench/trace_reduce.py``).

``jax.profiler.ProfileData`` gives each device op's event but not the
stats of its metadata, where the op's name stack is kept (the ``tf_op``
stat, e.g. ``jit(micro_value_and_grad)/transpose(jvp(agg))/u2i/...``),
so ``tf_ops`` reads those from the ``.xplane.pb`` bytes with a minimal
protobuf wire reader.  A transform wraps the first scope it applies to
(``jvp(agg)``), so ``in_scope`` looks for ``agg`` as a name-stack
component inside any such wrapping.

The harness hands its readers the trace's reduction, not the trace
file; ``of_run`` finds the file in the harness's temporary trace
directory and takes it only if its window and step count are the
reduction's.  A trace of a program without the scopes or spans reads
None for what it lacks.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import struct
import tempfile

from bench import trace_reduce as tr

AGG_SCOPE = "agg"
BATCH_SPAN = "train.batch"
TF_OP = "tf_op"
# where bench/harness.py writes the window's trace
TRACE_DIRS = "bench_trace_*"
CANDIDATES = 4

# XSpace, XPlane, XEventMetadata, XStatMetadata, XStat field numbers
# (tsl/profiler/protobuf/xplane.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_MD, PLANE_STAT_MD = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
MD_NAME, EVENT_MD_STATS = 2, 5
STAT_MD_ID, STAT_STR, STAT_REF = 1, 5, 7
WRAPPED = re.compile(r"^[\w.-]*\((.*)\)$")


# ------------------------------------------------------------ wire reader
def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        if i >= len(buf):
            raise ValueError("truncated varint")
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: ints for
    varints and fixed widths, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        if i > n:
            raise ValueError("truncated field")
        yield num, value


def _map_entries(entries):
    """(key, value message) of each entry of a proto map field."""
    for entry in entries:
        key = value = None
        for num, v in _fields(entry):
            if num == MAP_KEY:
                key = v
            elif num == MAP_VALUE:
                value = v
        yield key, value if value is not None else b""


def _name(message) -> str:
    for num, v in _fields(message):
        if num == MD_NAME:
            return bytes(v).decode()
    return ""


def tf_ops(data: bytes) -> dict:
    """Plane name -> {event metadata name: its ``tf_op`` stat}, for the
    metadata that carry one.  The metadata's name is what
    ``ProfileData`` gives as the event's name (a device op's HLO
    text)."""
    out = {}
    for num, plane in _fields(memoryview(data)):
        if num != SPACE_PLANES:
            continue
        name, event_md, stat_md = "", [], {}
        for pnum, v in _fields(plane):
            if pnum == PLANE_NAME:
                name = bytes(v).decode()
            elif pnum == PLANE_EVENT_MD:
                event_md.append(v)
            elif pnum == PLANE_STAT_MD:
                for key, md in _map_entries([v]):
                    stat_md[key] = _name(md)
        tf_id = next((k for k, n in stat_md.items() if n == TF_OP), None)
        ops = {}
        if tf_id is not None:
            for _, md in _map_entries(event_md):
                md_name, op = "", None
                for mnum, v in _fields(md):
                    if mnum == MD_NAME:
                        md_name = bytes(v).decode()
                    elif mnum == EVENT_MD_STATS:
                        stat = dict(_fields(v))
                        if stat.get(STAT_MD_ID) == tf_id:
                            op = (bytes(stat[STAT_STR]).decode()
                                  if STAT_STR in stat
                                  else stat_md.get(stat.get(STAT_REF)))
                if op:
                    ops[md_name] = op
        out[name] = ops
    return out


# ------------------------------------------------------------- name stack
def _components(stack: str):
    """Name-stack components split at ``/`` outside parentheses."""
    depth, start = 0, 0
    for i, ch in enumerate(stack):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            yield stack[start:i]
            start = i + 1
    yield stack[start:]


def in_scope(tf_op: str, scope: str = AGG_SCOPE) -> bool:
    """Whether ``scope`` is a component of an op's name stack, bare or
    wrapped by transforms (``transpose(jvp(agg))``).  A ``tf_op`` may
    end in ``:<type>``, which is not part of the stack."""
    stack = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    for comp in _components(stack):
        while True:
            if comp == scope:
                return True
            m = WRAPPED.match(comp)
            if not m:
                break
            comp = m.group(1)
    return False


# -------------------------------------------------------------- reduction
@dataclasses.dataclass
class Scopes:
    n_steps: int
    agg_s: list           # per chip: device seconds of ops under agg
    agg_compute_s: list   # per chip: the same, collective ops excluded
    batch_s: float | None         # host seconds inside train.batch spans
    batch_idle_s: list | None     # per chip: idle seconds inside them

    def _ms(self, seconds: float) -> float:
        return 1e3 * seconds / self.n_steps

    def agg_ms(self) -> float | None:
        """Device ms per step under ``agg``, mean over chips."""
        if not any(self.agg_s):
            return None
        return self._ms(sum(self.agg_s) / len(self.agg_s))

    def agg_skew_ms(self) -> float | None:
        """Slowest less fastest chip's ms per step under ``agg``,
        collectives excluded; None on one chip."""
        if len(self.agg_compute_s) < 2 or not any(self.agg_compute_s):
            return None
        return self._ms(max(self.agg_compute_s) - min(self.agg_compute_s))

    def batch_ms(self) -> float | None:
        """Host ms per step inside ``train.batch`` spans."""
        return None if self.batch_s is None else self._ms(self.batch_s)

    def batch_exposed_ms(self) -> float | None:
        """Device idle ms per step inside ``train.batch`` spans, mean
        over chips."""
        if self.batch_idle_s is None:
            return None
        return self._ms(sum(self.batch_idle_s) / len(self.batch_idle_s))


def _window(pd):
    host = tr._host_events(list(pd.planes))
    steps = [(s, e) for n, s, e in host if n == tr.STEP_SPAN]
    if not steps:
        return None
    return host, len(steps), min(s for s, _ in steps), max(e for _, e in steps)


def reduce_scopes(pd, data: bytes, red,
                  scope: str = AGG_SCOPE) -> Scopes | None:
    """The scope and span numbers of the trace ``pd`` (``data``: its
    bytes) over the harness's reduction ``red`` of the same trace, the
    device time under ``scope``; None when the trace is another: its
    window, step count or clock is not ``red``'s."""
    found = _window(pd)
    if found is None:
        return None
    host, n_steps, lo, hi = found
    gaps = [iv for c in red.chips for iv in c.gaps]
    if (n_steps != red.n_steps or abs((hi - lo) * 1e-9 - red.window_s) > 1e-6
            or any(s < lo or e > hi for s, e in gaps)):
        return None
    scopes = tf_ops(data)
    devices = sorted((int(tr.DEVICE_PLANE.match(p.name).group(1)), p)
                     for p in pd.planes if tr.DEVICE_PLANE.match(p.name))
    agg, compute = [], []
    for _, plane in devices[:len(red.chips)]:
        ops = scopes.get(plane.name, {})
        total = coll = 0.0
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            for full, s, e in tr._clip(tr._events(line), lo, hi):
                short = tr.OP_NAME.match(full).group(1)
                if tr.CONTAINER.match(short) or not in_scope(
                        ops.get(full, ""), scope):
                    continue
                total += (e - s) * 1e-9
                if tr.COLLECTIVE.match(short):
                    coll += (e - s) * 1e-9
        agg.append(total)
        compute.append(total - coll)
    spans = tr._union((s, e) for n, s, e in tr._clip(host, lo, hi)
                      if n == BATCH_SPAN)
    batch_s = idle = None
    if spans:
        batch_s = tr._length(spans) * 1e-9
        idle = [(tr._length(c.gaps) - tr._subtract(c.gaps, spans)) * 1e-9
                for c in red.chips]
    return Scopes(n_steps=n_steps, agg_s=agg, agg_compute_s=compute,
                  batch_s=batch_s, batch_idle_s=idle)


def _find(red) -> Scopes | None:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**",
                                   "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime)[::-1][:CANDIDATES]:
        try:
            with open(path, "rb") as f:
                data = f.read()
            found = reduce_scopes(ProfileData.from_serialized_xspace(data),
                                  data, red)
        except (OSError, ValueError):
            continue
        if found is not None:
            return found
    return None


def of_run(ctx: dict) -> Scopes | None:
    """The scope numbers of this run's trace, found once and kept in
    ``ctx`` for the other readers; None without a trace to read."""
    if "scopes" not in ctx:
        ctx["scopes"] = _find(ctx["reduction"]) if "reduction" in ctx \
            else None
    return ctx["scopes"]
