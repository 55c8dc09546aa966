"""The per-layer numbers read from the program's own spans and scopes
(``bench/scope_trace.py`` and its readers): the wire reader and the
scope matcher on a trace built as a text proto, on the two traces kept
from the chip, and on the op metadata of the compiled training step;
and the compile counter's reader after a run off the chip."""
import json
import os
import subprocess
import sys

import pytest

from bench import scope_trace, trace_reduce
from bench.manifest import ROOT, Manifest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("agg.ms", "agg_roofline", "agg.skew_ms", "host.batch_ms",
       "host.batch_exposed_ms")


def _event(md, start, end):
    return (f"events {{ metadata_id: {md} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} }}")


def _plane(pid, name, lines, metadata, stat_metadata=""):
    body = "".join(
        f'lines {{ id: {i} name: "{ln}" timestamp_ns: 0 '
        + " ".join(_event(*ev) for ev in evs) + " }"
        for i, (ln, evs) in enumerate(lines.items(), 1))
    mds = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" {s} }} }}'
        for k, (n, s) in metadata.items())
    return (f'planes {{ id: {pid} name: "{name}" {body} {mds} '
            f"{stat_metadata} }}")


TF_OP = 'stat_metadata { key: 7 value { id: 7 name: "tf_op" } }'
AGG_OP = "jit(m)/jvp(agg)/u2i/jit(spmm_csr_pallas)/spmm_sum/pallas_call:"
PERMUTE_OP = "jit(m)/transpose(jvp(agg))/sym/shard_map/ppermute:"
LOSS_OP = "jit(m)/jvp()/gather:"


def _device_metadata(by_ref: bool):
    """Chip 0 carries ``tf_op`` as a string, chip 1 as a reference to
    an interned stat metadata name: the trace writer does either."""
    def stat(op, key):
        value = f"ref_value: {key}" if by_ref else f'str_value: "{op}"'
        return f"stats {{ metadata_id: 7 {value} }}"
    ops = {1: ("%spmm_sum.1 = f32[8]{0} custom-call()", AGG_OP),
           2: ("%collective-permute-start.1 = f32[8]{0} "
               "collective-permute-start()", PERMUTE_OP),
           3: ("%fusion.1 = f32[8]{0} fusion()", LOSS_OP),
           4: ("%while.1 = f32[8]{0} while()", AGG_OP)}
    md = {k: (n, stat(op, 20 + k)) for k, (n, op) in ops.items()}
    interned = "".join(
        f'stat_metadata {{ key: {20 + k} value {{ id: {20 + k} '
        f'name: "{op}" }} }}' for k, (_, op) in ops.items()) if by_ref \
        else ""
    return md, TF_OP + interned


def text_trace(batch_spans=True, scopes=True, shift=0):
    """Two steps of 1000 ns on two chips.  Chip 0: an ``agg`` kernel,
    an ``agg`` permute, a loss op outside it and a ``while`` around the
    first two; chip 1 less ``agg`` work.  ``train.batch`` spans cover
    50-250 and 900-1250."""
    host_events = [(1, 0, 1000), (1, 1000, 2000)]
    if batch_spans:
        host_events += [(2, 50, 250), (2, 900, 1250)]
    host = _plane(1, "/host:CPU", {"python": [
        (m, s + shift, e + shift) for m, s, e in host_events]},
        {1: ("bench.step", ""), 2: ("train.batch", "")})
    planes = [host]
    chips = {0: [(4, 300, 760), (1, 300, 700), (2, 700, 750),
                 (3, 750, 800), (1, 1300, 1600), (3, 1600, 1650)],
             1: [(1, 300, 600), (2, 600, 650), (1, 1200, 1400)]}
    for chip, evs in chips.items():
        md, stat_md = _device_metadata(by_ref=chip == 1)
        if not scopes:
            md = {k: (n, "") for k, (n, _) in md.items()}
        planes.append(_plane(
            2 + chip, f"/device:TPU:{chip}",
            {"XLA Ops": [(m, s + shift, e + shift) for m, s, e in evs]},
            md, stat_md))
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(" ".join(planes))


def _reduced(data, n_chips=2):
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(data)
    return pd, trace_reduce.reduce_profile(pd, n_chips)


def test_wire_reader_finds_each_ops_name_stack():
    ops = scope_trace.tf_ops(text_trace())
    assert ops["/host:CPU"] == {}
    for chip in ("/device:TPU:0", "/device:TPU:1"):
        assert ops[chip]["%spmm_sum.1 = f32[8]{0} custom-call()"] == AGG_OP
        assert ops[chip]["%fusion.1 = f32[8]{0} fusion()"] == LOSS_OP


@pytest.mark.parametrize("tf_op, under", [
    (AGG_OP, True), (PERMUTE_OP, True), (LOSS_OP, False),
    ("jit(m)/agg/edge/gather", True),
    ("jit(m)/transpose(jvp(jit(agg)))/hadamard/x:", True),
    ("jit(m)/jvp(aggregate)/u2i/gather:", False),
    ("jit(m)/jvp(jit(f))/x/agg_u2i:", False),
    ("", False)])
def test_scope_matcher(tf_op, under):
    assert scope_trace.in_scope(tf_op) is under


def test_reduction_of_a_text_trace():
    data = text_trace()
    pd, red = _reduced(data)
    sc = scope_trace.reduce_scopes(pd, data, red)
    assert sc.n_steps == 2
    # the while counts towards busy time only
    assert sc.agg_s == pytest.approx([750e-9, 550e-9])
    assert sc.agg_compute_s == pytest.approx([700e-9, 500e-9])
    assert sc.batch_s == pytest.approx(550e-9)
    # chip 0 idle 0-300, 800-1300; chip 1 idle 0-300, 650-1200
    assert sc.batch_idle_s == pytest.approx([550e-9, 500e-9])
    assert sc.agg_ms() == pytest.approx(1e3 * 650e-9 / 2)
    assert sc.agg_skew_ms() == pytest.approx(1e3 * 200e-9 / 2)
    assert sc.batch_ms() == pytest.approx(1e3 * 550e-9 / 2)
    assert sc.batch_exposed_ms() == pytest.approx(1e3 * 525e-9 / 2)


def test_what_the_trace_lacks_reads_none():
    data = text_trace(batch_spans=False, scopes=False)
    pd, red = _reduced(data)
    sc = scope_trace.reduce_scopes(pd, data, red)
    assert (sc.agg_ms(), sc.agg_skew_ms(), sc.batch_ms(),
            sc.batch_exposed_ms()) == (None, None, None, None)
    pd, red = _reduced(data, n_chips=1)
    assert scope_trace.reduce_scopes(pd, data, red).agg_skew_ms() is None


def test_another_runs_trace_is_not_taken():
    """A trace of as many steps over as long a window, later on the
    clock, is another run's; so is one of another step count."""
    _, red = _reduced(text_trace())
    later = text_trace(shift=5000)
    pd, _ = _reduced(later)
    assert scope_trace.reduce_scopes(pd, later, red) is None
    data = text_trace()
    pd, red = _reduced(data)
    red.n_steps = 3
    assert scope_trace.reduce_scopes(pd, data, red) is None


def _write(root, name, data):
    d = root / name / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(data)


def test_readers_find_the_runs_trace(tmp_path, monkeypatch):
    """The readers take the trace from the harness's temporary trace
    directory, the one whose window is the reduction's, and read it
    once for all of them."""
    monkeypatch.setattr(scope_trace.tempfile, "tempdir", str(tmp_path))
    data = text_trace()
    _, red = _reduced(data)
    _write(tmp_path, "bench_trace_a", data)
    # a later trace of another window and another program
    _write(tmp_path, "bench_trace_b", text_trace(shift=5000, scopes=False))
    man = Manifest()
    ctx = {"reduction": red, "spmm_step_bytes": 819,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    got = {n: man.metric_reader(n).read(ctx) for n in NEW}
    assert got == pytest.approx({
        "agg.ms": 3.25e-4, "agg_roofline": 100 * 1e-9 / 325e-9,
        "agg.skew_ms": 1e-4, "host.batch_ms": 2.75e-4,
        "host.batch_exposed_ms": 2.625e-4})
    assert isinstance(ctx["scopes"], scope_trace.Scopes)


def test_readers_without_the_runs_trace_read_none(tmp_path, monkeypatch):
    monkeypatch.setattr(scope_trace.tempfile, "tempdir", str(tmp_path))
    _write(tmp_path, "bench_trace_b", text_trace(shift=5000))
    _, red = _reduced(text_trace())
    man = Manifest()
    ctx = {"reduction": red}
    assert [man.metric_reader(n).read(ctx) for n in NEW] == [None] * 5


# ----------------------------------------------------- traces from the chip
def _kept(cell, n_chips):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, f"{cell}.xplane.pb"), "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    return pd, data, trace_reduce.reduce_profile(pd, n_chips)


def test_kept_one_chip_trace():
    """The kept trace predates the scopes: the new metrics read None,
    the existing ones what ``test_bench_trace.py`` pins, and the
    matcher on the kernel's own jit (the same ops ``agg`` now wraps)
    reads ``spmm.ms``."""
    pd, data, red = _kept("lightgcn-m25-train", 1)
    sc = scope_trace.reduce_scopes(pd, data, red)
    assert (sc.agg_ms(), sc.batch_ms(), sc.batch_exposed_ms()) == \
        (None, None, None)
    man = Manifest()
    ctx = {"reduction": red, "chips": 1, "scopes": sc,
           "spmm_step_bytes": 2_683_523_568, "step_flops": 1,
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    assert man.metric_reader("spmm.ms").read(ctx) == \
        pytest.approx(5460.482426333)
    assert man.metric_reader("engine.micro_ms").read(ctx) == \
        pytest.approx(5475.181550333)
    assert man.metric_reader("engine.update_ms").read(ctx) == \
        pytest.approx(1.897155)
    assert man.metric_reader("device.idle_share").read(ctx) == \
        pytest.approx(100 * (1 - 16.433102621 / 16.821072849))
    assert man.metric_reader("ring.permute_ms").read(ctx) is None
    assert man.metric_reader("spmm_roofline").read(ctx) == pytest.approx(
        100 * 2_683_523_568 / 819e9 / 5.460482426333)
    assert man.metric_reader("train_mfu").read(ctx) == pytest.approx(
        100 / (16.821072849 / 3 * 197e12))
    assert [man.metric_reader(n).read(ctx) for n in NEW] == [None] * 5
    kernel = scope_trace.reduce_scopes(pd, data, red, "spmm_csr_pallas")
    assert kernel.agg_ms() == pytest.approx(5460.482426333, rel=1e-4)


def test_kept_four_chip_trace():
    """The ring's work by chip, read by the matcher under the ring's
    ``shard_map`` (which ``agg/sym`` now wraps): one chip has about 264
    ms a step less aggregation than the others, the time it waits at
    the permutes."""
    pd, data, red = _kept("lightgcn-m25-ring4-train", 4)
    sc = scope_trace.reduce_scopes(pd, data, red)
    assert sc.agg_ms() is None and sc.agg_skew_ms() is None
    man = Manifest()
    ctx = {"reduction": red, "scopes": sc}
    assert man.metric_reader("ring.permute_ms").read(ctx) == \
        pytest.approx(337.121288725)
    assert man.metric_reader("ring.exposed_ms").read(ctx) == \
        pytest.approx(281.6853247)
    assert man.metric_reader("engine.micro_ms").read(ctx) == \
        pytest.approx(3675.242622675)
    assert [man.metric_reader(n).read(ctx) for n in NEW] == [None] * 5
    ring = scope_trace.reduce_scopes(pd, data, red, "shard_map")
    per_chip = [1e3 * s / ring.n_steps for s in ring.agg_compute_s]
    assert per_chip == pytest.approx([3378.2126, 3394.4736, 3130.3006,
                                      3387.6612], rel=1e-6)
    assert ring.agg_skew_ms() == pytest.approx(264.1730635, rel=1e-6)


# ------------------------------------------------- the compiled step's ops
SCRIPT = """
import collections, dataclasses, json, re, sys
sys.path[:0] = [{root!r}, {src!r}]
from repro.data import synth
from repro.pipeline import engine
from repro.pipeline.engine import PipelineConfig, build_pipeline
from repro.pipeline.registry import MODELS

OP = re.compile(r'^\\s*(?:ROOT )?%\\S+ = .*? (gather|scatter|'
                r'collective-permute-start|collective-permute|custom-call)'
                r'\\(.*?op_name="([^"]*)"')


def ops(arch, ring, hadamard, identity):
    spec = MODELS[arch]
    if identity:
        spec = dataclasses.replace(
            spec, forward=lambda p, g, n: (p["user_embed"], p["item_embed"]))
    engine.get_model = lambda name: spec
    kw = dict(mesh_shape=(4,), spmm="ring") if ring else {{}}
    data = synth.generate_bipartite(40, 30, 300, seed=0)
    pipe = build_pipeline(PipelineConfig(
        arch=arch, embed_dim=8, microbatch=16, hadamard=hadamard,
        target_batch=16 * (4 if ring else 1), **kw), data)
    u, p, n = pipe._next_target_batch(1, 0)
    with pipe.step_context():
        text = pipe._micro_value_and_grad.lower(
            pipe.init_state()["params"], pipe.g,
            *pipe._device_batch(u, p, n)).compile().as_text()
    return [m.groups() for m in map(OP.match, text.splitlines()) if m]


out = {{}}
for case in {cases!r}:
    out["/".join(map(str, case))] = [ops(*case, identity=False),
                                     ops(*case, identity=True)]
print(json.dumps(out))
"""
CASES = [("lightgcn", False, "auto"), ("lightgcn", True, "auto"),
         ("ngcf", False, "fused"), ("ngcf", False, "composed")]
KINDS = {"lightgcn/False/auto": {"u2i", "i2u"}, "lightgcn/True/auto": {"sym"},
         "ngcf/False/fused": {"u2i", "i2u", "hadamard"},
         "ngcf/False/composed": {"u2i", "i2u", "edge"}}


@pytest.fixture(scope="module")
def step_ops():
    """Each case's gathers, scatters, permutes and custom calls (opcode,
    op_name) in the compiled ``micro_value_and_grad``, and the same for
    the step with no aggregation at all (the forward returns the
    tables): XLA route, and the ring on four virtual CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                           cases=CASES)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def _kinds(op_name):
    """(direction, kind) of an op under ``agg``, by the matcher."""
    comps = list(scope_trace._components(op_name))
    for i, c in enumerate(comps):
        if scope_trace.in_scope(c):
            return ("backward" if c.startswith("transpose(") else
                    "forward", comps[i + 1])
    return None


@pytest.mark.parametrize("case", sorted(KINDS))
def test_every_aggregation_op_carries_the_scope(step_ops, case):
    """Forward and backward, the kinds the route aggregates with are
    under ``agg``; and what lies outside it is the loss's own row
    lookups: the same ops as in the step with no aggregation.  NGCF's
    composed route builds its [E, D] Hadamard messages outside the
    graph's entry points, so there only the kinds are checked."""
    real, bare = step_ops[case]
    found = {_kinds(n) for _, n in real} - {None}
    want = KINDS[case]
    # XLA may merge one kind's op into another's (NGCF's forward u2i
    # gather is the fused Hadamard's), so each kind shows in at least
    # one direction and each direction shows
    assert found <= {(d, k) for d in ("forward", "backward") for k in want}
    assert {k for _, k in found} == want
    assert {d for d, _ in found} == {"forward", "backward"}
    if case.endswith("composed"):
        return
    outside = sorted(op for op, n in real if not scope_trace.in_scope(n))
    assert outside == sorted(op for op, _ in bare)
    if "/True/" in case:
        assert any(op.startswith("collective-permute") for op, _ in real)
        assert not any(op.startswith("collective-permute")
                       for op in outside)


# --------------------------------------------------------- compile counter
COUNTER = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
from bench.manifest import Manifest
cfg = Manifest().config("lightgcn-m25")
cfg.update(n_users=300, n_items=200, n_edges=3000, embed_dim=16,
           n_layers=2, bpr_batch=256, base_batch=16)
harness.run_cell("lightgcn-m25-train", 2**31 + 11, 0.05, False, cfg=cfg,
                 require_tpu=False)
print(json.dumps(Manifest().metric_reader("host.compiles").read({{}})))
"""


def test_compile_counter_reads_no_retrace_after_a_run():
    """After set-up and a window off the chip, the engine's two step
    programs have each lowered once: ``host.compiles`` reads 0.  (A
    process of its own: the counter counts from the first import.)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-c",
         COUNTER.format(root=str(ROOT), src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert json.loads(res.stdout.strip().splitlines()[-1]) == 0
