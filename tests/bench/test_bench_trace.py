"""The reduction from a profiler trace to per-chip numbers and the
per-layer metrics read from it."""
import os
import types

import pytest

from bench import trace_reduce
from bench.manifest import Manifest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# the four-chip trace's permute ms a step, per chip, and chip 0's exposed
PERMUTE_PER_CHIP = [281.6825491, 265.0583824, 529.8950651, 271.8491583]
EXPOSED_CHIP0 = 281.6853247


def _ev(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(end - start))


def _plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


OPS = [_ev("spmm_sum", 100, 500), _ev("fusion.2", 500, 800),
       _ev("collective-permute.1", 600, 700),
       _ev("collective-permute.1", 800, 820),
       _ev("add", 820, 860),
       _ev("spmm_sum.3", 1100, 1500), _ev("fusion.2", 1500, 1800),
       _ev("add", 1820, 1860)]


def fake_profile():
    host = _plane("/host:CPU", python=[
        _ev("bench.step", 0, 1000), _ev("bench.step", 1000, 2000),
        _ev("$loader.py:51 _epoch_perm", 880, 1100)])
    # one in-flight permute hidden under compute, one that is not
    in_flight = [_ev("collective-permute-start.1", 590, 710),
                 _ev("collective-permute-start.1", 860, 900)]
    chip = _plane("/device:TPU:0", XLA_Modules=[
        _ev("jit_micro_value_and_grad(12)", 100, 800),
        _ev("jit_apply_update(3)", 820, 860),
        _ev("jit_micro_value_and_grad(12)", 1100, 1800),
        _ev("jit_apply_update(3)", 1820, 1860)],
        XLA_Ops=OPS, Async_XLA_Ops=in_flight)
    # a second chip whose trace, like the chip's for all but the first,
    # records no in-flight spans
    other = _plane("/device:TPU:1", XLA_Ops=OPS)
    return types.SimpleNamespace(planes=[host, chip, other])


def test_reduction_of_a_small_trace():
    red = trace_reduce.reduce_profile(fake_profile(), n_chips=1)
    c = red.chips[0]
    assert red.window_s == pytest.approx(2000e-9)
    assert red.n_steps == 2
    assert c.busy_s == pytest.approx(1500e-9)
    assert c.collective_s == pytest.approx(120e-9)
    assert sorted(c.collective_ops) == pytest.approx([20e-9, 100e-9])
    # 800-820 and the 860-900 in flight; 600-700 runs under fusion.2
    assert c.exposed_s == pytest.approx(60e-9)
    assert red.ops_ms("spmm_") == pytest.approx(1e3 * 800e-9 / 2)
    assert red.module_ms("jit_apply_update") == pytest.approx(1e3 * 40e-9)
    assert red.module_ms("jit_nothing") is None
    gaps = red.breakdown["idle_gaps"]
    assert gaps[0] == ["chip0: $loader.py:51 _epoch_perm",
                       pytest.approx(240e-9)]
    assert red.breakdown["device_ops"][0] == ["fusion.2",
                                              pytest.approx(600e-9)]


def test_metrics_read_from_the_reduction():
    man = Manifest()
    red = trace_reduce.reduce_profile(fake_profile(), n_chips=1)
    ctx = {"reduction": red, "chips": 1, "spmm_step_bytes": 819,
           "step_flops": 197e3,
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda n: man.metric_reader(n).read(ctx)
    assert read("device.idle_share") == pytest.approx(100 * 500 / 2000)
    # 1 ns of least time over 400 ns of kernel per step
    assert read("spmm_roofline") == pytest.approx(100 / 400)
    assert read("train_mfu") == pytest.approx(100 * 1e-9 / 1000e-9)
    assert read("ring.permute_ms") == pytest.approx(1e3 * 60e-9)
    assert read("ring.exposed_ms") == pytest.approx(1e3 * 30e-9)
    assert read("engine.micro_ms") == pytest.approx(1e3 * 700e-9)


def test_exposure_only_where_in_flight_spans_are_recorded():
    """A chip with no in-flight spans has no exposed time, and the
    metric is the mean over the chips that have one."""
    man = Manifest()
    red = trace_reduce.reduce_profile(fake_profile(), n_chips=2)
    assert red.chips[1].exposed_s is None
    assert red.chips[1].collective_s == pytest.approx(120e-9)
    ctx = {"reduction": red}
    assert man.metric_reader("ring.exposed_ms").read(ctx) == \
        pytest.approx(1e3 * 30e-9)
    assert man.metric_reader("ring.permute_ms").read(ctx) == \
        pytest.approx(1e3 * 60e-9)


def test_a_trace_without_step_spans_is_refused():
    pd = fake_profile()
    pd.planes[0].lines[0].events = pd.planes[0].lines[0].events[2:]
    with pytest.raises(ValueError, match="bench.step"):
        trace_reduce.reduce_profile(pd, n_chips=1)


def test_reduction_of_a_trace_recorded_on_the_chip():
    """Three steps of ``lightgcn-m25-train`` traced on one TPU v5 lite
    (``--trace 1``): the 12 Pallas ``spmm_sum`` calls are the step, the
    loader's permutation is the idle gap between steps."""
    from jax.profiler import ProfileData
    path = os.path.join(DATA, "lightgcn-m25-train.xplane.pb")
    red = trace_reduce.reduce_profile(ProfileData.from_file(path),
                                      n_chips=1)
    assert red.n_steps == 3
    assert red.window_s == pytest.approx(16.821072849)
    assert red.busy_s == pytest.approx(16.433102621)
    assert red.ops_ms("spmm_") == pytest.approx(5460.482426333)
    assert red.module_ms("jit_micro_value_and_grad") == \
        pytest.approx(5475.181550333)
    assert red.module_ms("jit_apply_update") == pytest.approx(1.897155)
    assert red.chips[0].collective_s == 0.0
    top = red.breakdown["device_ops"]
    assert len(top) == 10 and all(n.startswith("spmm_sum.") for n, _ in top)
    gaps = red.breakdown["idle_gaps"]
    assert gaps[0][0] == "chip0: $loader.py:35 _epoch_perm"
    assert gaps[0][1] == pytest.approx(0.144403714)


def test_reduction_of_a_four_chip_trace_recorded_on_the_chip():
    """Ten steps of ``lightgcn-m25-ring4-train`` traced on four TPU v5
    lite: the ring's permute ops are on every chip's ``XLA Ops`` line,
    their in-flight spans on the first chip's only; the ``while`` loops
    that hold them count only as busy time, and the loader's
    permutation is the idle gap."""
    from jax.profiler import ProfileData
    path = os.path.join(DATA, "lightgcn-m25-ring4-train.xplane.pb")
    red = trace_reduce.reduce_profile(ProfileData.from_file(path),
                                      n_chips=4)
    assert red.n_steps == 10 and len(red.chips) == 4
    assert red.window_s == pytest.approx(44.139816727)
    assert red.busy_s == pytest.approx(36.75897363175)
    permute = red.per_step(c.collective_s for c in red.chips)
    assert permute == pytest.approx(337.121288725)
    assert [c.exposed_s is None for c in red.chips] == \
        [False, True, True, True]
    assert red.per_step([red.chips[0].exposed_s]) == \
        pytest.approx(EXPOSED_CHIP0)
    # each chip waits in a few permutes a step, one chip twice as long
    per_chip = [1e3 * c.collective_s / red.n_steps for c in red.chips]
    assert per_chip == pytest.approx(PERMUTE_PER_CHIP)
    assert red.module_ms("jit_micro_value_and_grad") == \
        pytest.approx(3675.242622675)
    assert red.ops_ms("spmm_") is None
    assert not any(n.startswith("while") for n, _ in
                   red.breakdown["device_ops"])
    assert red.breakdown["idle_gaps"][0][0].endswith("_epoch_perm")
