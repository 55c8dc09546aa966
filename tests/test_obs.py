"""``repro.obs``: the compile counter, the engine's program names that
the benchmark's readers key on, and the host spans of a step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.data import synth
from repro.pipeline.engine import PipelineConfig, build_pipeline


def _lowerings(name: str) -> int:
    return obs.counters()["lowerings"].get(name, 0)


def test_a_new_shape_lowers_once_and_a_repeat_not_at_all():
    @jax.jit
    def obs_probe(x):
        return jnp.sin(x) * 2

    name = "jit(obs_probe)"
    before = _lowerings(name)
    obs_probe(jnp.ones(3))
    assert _lowerings(name) == before + 1
    obs_probe(jnp.ones(3))
    assert _lowerings(name) == before + 1
    obs_probe(jnp.ones(4))
    assert _lowerings(name) == before + 2
    compiles = obs.counters()["compiles"]
    assert compiles[name] >= 2


def test_the_snapshot_is_a_copy():
    snap = obs.counters()
    snap["lowerings"]["jit(nothing)"] = 99
    assert "jit(nothing)" not in obs.counters()["lowerings"]


def test_the_pallas_spmm_counts_its_ring_depth_once_a_shape():
    """``spmm_inflight`` counts, by DMA ring depth, one trace of the
    Pallas SpMM per call shape; the XLA route counts nothing."""
    from repro.kernels import ops as kops
    from repro.kernels.spmm import build_csr_by_dst, ring_plan

    rng = np.random.default_rng(0)

    def call(n, e, impl):
        dst = rng.integers(0, n, e).astype(np.int32)
        src = rng.integers(0, n, e).astype(np.int32)
        indptr, src_sorted, _ = build_csr_by_dst(dst, src, n)
        x = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
        return kops.spmm_csr("sum", x, jnp.asarray(indptr),
                             jnp.asarray(src_sorted), n, gather=True,
                             impl=impl)

    depth = ring_plan(11, 8)[1]
    assert ring_plan(13, 8)[1] == depth

    def inflight():
        return obs.counters()["spmm_inflight"].get(depth, 0)

    before = inflight()
    call(11, 29, "pallas")
    call(11, 29, "pallas")
    assert inflight() == before + 1
    call(13, 31, "pallas")
    assert inflight() == before + 2
    snap = obs.counters()["spmm_inflight"]
    call(17, 37, "xla")
    assert obs.counters()["spmm_inflight"] == snap


def test_an_unknown_counter_is_refused():
    with pytest.raises(ValueError, match="counter"):
        obs.count("everything", 1)


def test_an_unknown_aggregation_kind_is_refused():
    with pytest.raises(ValueError, match="aggregation kind"):
        with obs.agg_scope("everything"):
            pass


@pytest.fixture(scope="module")
def pipe():
    data = synth.generate_bipartite(40, 30, 300, seed=0)
    return build_pipeline(PipelineConfig(embed_dim=8, microbatch=16,
                                         target_batch=16), data)


def test_engine_programs_keep_the_names_the_benchmark_reads(pipe):
    """``engine.micro_ms``/``engine.update_ms`` read the programs'
    module names off the device trace, and ``host.compiles`` their
    lowerings off the counter: a rename fails here."""
    state = pipe.init_state()
    before = {n: _lowerings(f"jit({n})")
              for n in ("micro_value_and_grad", "apply_update")}
    u, p, n = pipe._next_target_batch(1, 0)
    micro = pipe._micro_value_and_grad.lower(
        state["params"], pipe.g, *pipe._device_batch(u, p, n))
    grads = state["params"]
    update = pipe._apply_update.lower(state, grads, jnp.float32(0.1))
    for name, lowered in (("micro_value_and_grad", micro),
                          ("apply_update", update)):
        text = lowered.compile().as_text()
        assert text.startswith(f"HloModule jit_{name},"), text[:80]
        assert _lowerings(f"jit({name})") == before[name] + 1


def test_a_step_runs_under_its_spans(pipe, monkeypatch):
    """``train.step`` wraps the step, ``train.batch`` the batch draw and
    ``train.loss_sync`` the loss read-back, each with the step number."""
    seen = []

    class Recorder:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            seen.append(("enter", self.name, self.kw))

        def __exit__(self, *exc):
            seen.append(("exit", self.name, self.kw))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    state, loss = pipe.step_fn(pipe.init_state(), 0)
    assert np.isfinite(loss)
    step = {"step": 0}
    assert seen == [("enter", obs.STEP_SPAN, step),
                    ("enter", obs.BATCH_SPAN, step),
                    ("exit", obs.BATCH_SPAN, step),
                    ("enter", obs.LOSS_SYNC_SPAN, step),
                    ("exit", obs.LOSS_SYNC_SPAN, step),
                    ("exit", obs.STEP_SPAN, step)]
