"""Reduce a profiler trace of the measured window to per-chip numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData``.  A device plane is ``/device:TPU:<n>``;
its ``XLA Ops`` line holds one event per device operation (named by its
HLO text, ``%spmm_sum.19 = f32[...] custom-call(...)``, of which the
op name is kept), its ``Async XLA Ops`` line one event per asynchronous
op from its start to its done, and its ``XLA Modules`` line one event
per jitted program run.  The window is the span of the harness's own host
annotations (``bench.step``, one per ``Run.step``), on the same clock.

Per chip: busy time (the union of operation intervals inside the
window), device time by operation name (control-flow ops such as
``while``, whose events span their bodies' ops, count only towards busy
time) and by program name, the union
of collective-permute op intervals, and the exposed ring time: those
intervals together with the permutes' in-flight spans, less the time
any other operation runs.  The trace records in-flight spans (an
``Async XLA Ops`` line) for the first chip only; on a chip without them
the exposed time would equal the permute ops' time by construction, so
it is left unknown there.  Besides, a ``breakdown``: the
operations that took most time, and the longest idle gaps, each named
by the innermost host event around its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

STEP_SPAN = "bench.step"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"^collective-permute")
# control-flow ops whose events span the ops of their bodies
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
OP_NAME = re.compile(r"^%?([^\s=]+)")
MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")
TOP = 10


@dataclasses.dataclass
class Chip:
    busy_s: float
    op_s: dict            # HLO op name -> seconds inside the window
    module_s: dict        # jitted program name -> seconds
    collective_s: float   # union of collective-permute op intervals
    collective_ops: list  # each collective-permute op's seconds
    exposed_s: float | None  # permute ops and in-flight permutes, less
    #                          the time other operations run; None where
    #                          the chip's trace has no in-flight spans
    gaps: list            # (start_ns, end_ns) idle intervals


@dataclasses.dataclass
class Reduction:
    window_s: float
    n_steps: int
    chips: list
    breakdown: dict

    @property
    def busy_s(self) -> float:
        return sum(c.busy_s for c in self.chips) / len(self.chips)

    def per_step(self, values) -> float:
        """Mean over chips of a per-chip seconds value, per step, in ms."""
        vals = list(values)
        return 1e3 * sum(vals) / len(vals) / self.n_steps

    def ops_ms(self, prefix: str) -> float | None:
        """Device ms per step of the operations whose names start with
        ``prefix``; None where no chip ran one."""
        per = [sum(v for n, v in c.op_s.items() if n.startswith(prefix))
               for c in self.chips]
        return self.per_step(per) if any(per) else None

    def module_ms(self, name: str) -> float | None:
        """Device ms per step of one jitted program; None if absent."""
        per = [c.module_s.get(name, 0.0) for c in self.chips]
        return self.per_step(per) if any(per) else None


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def _clip(events, lo, hi):
    """(name, start, end) events cut to the window [lo, hi)."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _subtract(a, b) -> float:
    """Length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def _op_events(events):
    return [(OP_NAME.match(n).group(1), s, e) for n, s, e in events]


def _chip(plane, lo, hi) -> Chip:
    lines = {ln.name: _events(ln) for ln in plane.lines}
    every = _clip(_op_events(lines.get(OPS_LINE, [])), lo, hi)
    ops = [ev for ev in every if not CONTAINER.match(ev[0])]
    in_flight = _clip(_op_events(lines.get(ASYNC_LINE, [])), lo, hi)
    op_s: dict = {}
    for n, s, e in ops:
        op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
    module_s: dict = {}
    for n, s, e in _clip(lines.get(MODULES_LINE, []), lo, hi):
        name = MODULE_NAME.match(n).group(1)
        module_s[name] = module_s.get(name, 0.0) + (e - s) * 1e-9
    busy = _union((s, e) for _, s, e in every)
    permutes = [(s, e) for n, s, e in ops if COLLECTIVE.match(n)]
    flying = [(s, e) for n, s, e in in_flight if COLLECTIVE.match(n)]
    compute = _union((s, e) for n, s, e in ops if not COLLECTIVE.match(n))
    exposed = (_subtract(_union(permutes + flying), compute) * 1e-9
               if flying else None)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return Chip(busy_s=_length(busy) * 1e-9, op_s=op_s, module_s=module_s,
                collective_s=_length(_union(permutes)) * 1e-9,
                collective_ops=[(e - s) * 1e-9 for s, e in permutes],
                exposed_s=exposed, gaps=gaps)


def _host_events(planes):
    out = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out.extend((n, s, e) for n, s, e in _events(line) if e > s)
    return out


def _label(host, mid) -> str:
    """The innermost (shortest) host event around ``mid``."""
    best = None
    for n, s, e in host:
        if s <= mid < e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "no host event"


def reduce_profile(pd, n_chips: int) -> Reduction:
    planes = list(pd.planes)
    host = _host_events(planes)
    steps = [(s, e) for n, s, e in host if n == STEP_SPAN]
    if not steps:
        raise ValueError(f"no {STEP_SPAN!r} host spans in the trace")
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    devices = sorted((int(DEVICE_PLANE.match(p.name).group(1)), p)
                     for p in planes if DEVICE_PLANE.match(p.name))
    chips = [_chip(p, lo, hi) for _, p in devices[:n_chips]]
    if not chips:
        raise ValueError("no device planes in the trace")
    ops: dict = {}
    for c in chips:
        for n, v in c.op_s.items():
            ops[n] = ops.get(n, 0.0) + v / len(chips)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(((e - s, s, e, i) for i, c in enumerate(chips)
                   for s, e in c.gaps), reverse=True)[:TOP]
    idle = [[f"chip{i}: {_label(host, (s + e) / 2)}", d * 1e-9]
            for d, s, e, i in gaps]
    return Reduction(window_s=(hi - lo) * 1e-9, n_steps=len(steps),
                     chips=chips,
                     breakdown={"device_ops": [[n, v] for n, v in top_ops],
                                "idle_gaps": idle})


def find_xplane(tdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return paths[-1]


def reduce_dir(tdir: str, n_chips: int) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(tdir)), n_chips)
