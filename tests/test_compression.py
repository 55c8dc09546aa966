"""Convergence-parity suite for the compression subsystem
(``repro.optim.compression`` wired through ``CompressionCfg``).

The pinning discipline mirrors PRs 4-5: the default ``CompressionCfg()``
is the *identity* (bit-identical training, no compressor state), every
active scheme must track the exact trajectory within a stated fp32
tolerance over >= 20 steps, int8-stored capacity-tier tables round-trip
within their quantization scale, and the planner's quantized byte
pricing stays certified by the exact DP across every registered
topology.  Multi-device arms run in subprocesses with forced host
devices (``test_distributed.run_with_devices``), including an HLO
assertion that the int8 combine really lowers to an integer all-reduce.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CompressionCfg, ExperimentSpec, build, get_preset
from repro.memory import (AccessProfile, QuantizedHostResident, get_topology,
                          gnn_recsys_profiles, place_exact, place_greedy,
                          quantized_table_bytes, topology_names)
from repro.optim import compression as C
from repro.pipeline.compress import GradCompressor

from test_distributed import run_with_devices

_OV = {"loop.steps": 20, "plan.target_batch": 64, "plan.microbatch": 16,
       "plan.warmup_epochs": 0, "data.edges": 1200, "loop.ckpt_dir": None}


def _smoke(**overrides) -> ExperimentSpec:
    return get_preset("lightgcn-smoke").override({**_OV, **overrides})


def _losses(spec: ExperimentSpec, n: int = 20) -> list:
    run = build(spec)
    return [run.step() for _ in range(n)]


# ---------------------------------------------------------------- spec
def test_compression_cfg_roundtrip_and_validation():
    """CompressionCfg is a first-class spec section: exact JSON
    round-trip, defaults equal to the identity, unknown values raise."""
    spec = _smoke(**{"compression.grads": "topk", "compression.frac": 0.05,
                     "compression.embed_store": "int8"})
    again = ExperimentSpec.from_json(spec.to_json())
    assert again == spec
    assert again.compression.grads == "topk"
    assert again.compression.frac == 0.05
    # a pre-compression spec dict (no 'compression' key) loads to the
    # identity section — old saved specs keep meaning what they meant
    d = _smoke().to_dict()
    del d["compression"]
    assert ExperimentSpec.from_dict(d).compression == CompressionCfg()
    with pytest.raises(ValueError, match="compression.grads"):
        CompressionCfg(grads="fp16")
    with pytest.raises(ValueError, match="compression.frac"):
        CompressionCfg(frac=0.0)
    with pytest.raises(ValueError, match="compression.embed_store"):
        CompressionCfg(embed_store="int4")


def test_compression_cli_flags_equal_spec_overrides():
    from repro.launch.train import build_arg_parser, spec_from_args
    args = build_arg_parser().parse_args(
        ["--compress-grads", "int8", "--compress-frac", "0.05",
         "--embed-store", "int8"])
    spec = spec_from_args(args)
    assert spec.compression.grads == "int8"
    assert spec.compression.frac == 0.05
    assert spec.compression.embed_store == "int8"


def test_grad_compressor_validation():
    with pytest.raises(ValueError, match="unknown compression scheme"):
        GradCompressor("gzip")
    with pytest.raises(ValueError, match="frac"):
        GradCompressor("topk", frac=1.5)
    gc = GradCompressor("topk", frac=0.1, error_feedback=False)
    assert "topk" in gc.describe() and "+ef" not in gc.describe()


# ---------------------------------------------------------------- primitives
def test_quantize_roundtrip_error_bounded_by_scale():
    """Stochastic int8: |dequant(quant(g)) - g| < scale per element
    (floor/ceil rounding moves at most one quantization step)."""
    for seed in range(5):
        rng = np.random.default_rng(seed)
        g = (rng.standard_normal(512) * 10.0 ** rng.integers(-3, 3)) \
            .astype(np.float32)
        q, scale = C.quantize_int8(jnp.asarray(g), jax.random.PRNGKey(seed))
        err = np.abs(np.asarray(C.dequantize_int8(q, scale)) - g)
        assert err.max() <= float(scale) * (1 + 1e-5), seed


def test_quantize_stochastic_rounding_unbiased():
    """E[dequant] == g: the mean over independent keys converges to the
    original (the property that keeps EF-free int8 psum centered)."""
    g = jnp.asarray(np.random.default_rng(0)
                    .standard_normal(64).astype(np.float32))
    outs = [np.asarray(C.dequantize_int8(*C.quantize_int8(
        g, jax.random.PRNGKey(k)))) for k in range(400)]
    _, scale = C.quantize_int8(g, jax.random.PRNGKey(0))
    bias = np.abs(np.mean(outs, 0) - np.asarray(g)).max()
    assert bias < float(scale) * 0.15, bias


def test_topk_sparsify_densify_exact_reconstruction():
    """densify(sparsify(g)) + residual == g, exactly: kept entries are
    copied (never recomputed) and the supports are disjoint."""
    for seed in range(5):
        g = np.random.default_rng(seed).standard_normal((24, 7)) \
            .astype(np.float32)
        kept, idx, residual = C.topk_sparsify(jnp.asarray(g), 13)
        dense = C.topk_densify(kept, idx, g.shape)
        np.testing.assert_array_equal(np.asarray(dense)
                                      + np.asarray(residual), g)
        # the kept entries reconstruct exactly
        flat = np.asarray(dense).reshape(-1)
        np.testing.assert_array_equal(flat[np.asarray(idx)],
                                      np.asarray(kept))


def test_error_feedback_residual_carry_invariant():
    """ErrorFeedback.apply with a top-k compressor: compressed +
    residual == grads + carried error, exactly, every leaf."""
    rng = np.random.default_rng(0)
    grads = {"a": jnp.asarray(rng.standard_normal((11, 5))
                              .astype(np.float32)),
             "b": jnp.asarray(rng.standard_normal(17).astype(np.float32))}
    errors = jax.tree.map(lambda g: jnp.asarray(
        rng.standard_normal(g.shape).astype(np.float32)), grads)
    g_hat, new_e = C.ErrorFeedback.apply(grads, errors,
                                         C.make_topk_compressor(0.25))
    for k in grads:
        np.testing.assert_array_equal(
            np.asarray(g_hat[k] + new_e[k]),
            np.asarray(grads[k] + errors[k]))


def test_wire_bytes_pricing():
    assert C.wire_bytes(1000, "none") == 4000
    assert C.wire_bytes(1000, "int8") == 1004          # 1 B/elt + 1 scale
    assert C.wire_bytes(1000, "topk", frac=0.01) == 10 * 8   # k=10 (v,i)
    with pytest.raises(ValueError, match="unknown compression scheme"):
        C.wire_bytes(10, "zstd")
    comp, exact = GradCompressor("int8").wire_bytes_per_step(
        {"w": np.zeros((100, 4), np.float32)})
    assert (comp, exact) == (404, 1600)


# ---------------------------------------------------------------- storage
def test_quantized_table_roundtrip_within_scale():
    """Acceptance (3), storage half: an int8-stored table round-trips
    through dequant-on-gather with max abs error <= its per-row
    quantization scale, at ~1/4 the resident bytes."""
    rng = np.random.default_rng(0)
    table = (rng.standard_normal((64, 16)) *
             10.0 ** rng.integers(-2, 2, (64, 1))).astype(np.float32)
    q, scale = C.quantize_rows_int8(table)
    err = np.abs(C.dequantize_rows_int8(q, scale) - table)
    assert (err <= scale * (1 + 1e-5)).all()

    host = QuantizedHostResident(table)
    ids = rng.integers(0, 64, 37)
    np.testing.assert_array_equal(host.take(ids),
                                  C.dequantize_rows_int8(q, scale)[ids])
    np.testing.assert_array_equal(host.block(ids), host.take(ids))
    assert host.shape == table.shape and host.dtype == np.float32
    assert host.nbytes == table.nbytes // 4 + 64 * 4   # q + per-row scales
    np.testing.assert_array_equal(host.dense(),
                                  C.dequantize_rows_int8(q, scale))


def test_executor_int8_store_roundtrips_and_reports_bytes():
    """A demoted params table under embed_store='int8' lives as (q,
    scale) buffers whose fetch view equals the int8 round-trip."""
    from repro.memory import TieredExecutor
    from repro.memory.policies import get_policy
    table = np.random.default_rng(1).standard_normal((32, 8)) \
        .astype(np.float32)
    profs = [AccessProfile("params['t']", table.nbytes, pinned="slow")]
    plan = get_policy("greedy")(profs, get_topology("uniform"))
    ex = TieredExecutor(plan, prefixes=("params",), embed_store="int8")
    state, moved = ex.place({"params": {"t": jnp.asarray(table)}})
    assert moved == 1
    q, scale = C.quantize_rows_int8(table)
    np.testing.assert_array_equal(state["params"]["t"],
                                  C.dequantize_rows_int8(q, scale))
    assert ex.store_nbytes("params['t']") == q.nbytes + scale.nbytes
    assert "embed_store=int8(1)" in ex.describe()
    # commit re-quantizes: the carried state is always the round-trip
    state2 = ex.commit({"params": {"t": jnp.asarray(table * 2.0)}})
    q2, s2 = C.quantize_rows_int8(table * 2.0)
    np.testing.assert_array_equal(state2["params"]["t"],
                                  C.dequantize_rows_int8(q2, s2))
    with pytest.raises(ValueError, match="unknown embed_store"):
        TieredExecutor(plan, embed_store="int4")


# ---------------------------------------------------------------- planner
def test_planner_prices_int8_tables_at_quarter_bytes():
    """Acceptance (4), pricing half: quantized profiles carry ~1/4
    resident bytes off the fast tier and the per-tier ``used``
    accounting uses them there (dense bytes stay authoritative on the
    fast tier)."""
    profs = gnn_recsys_profiles(1000, 2000, 30000, 32, 2,
                                embed_store="int8")
    emb = next(p for p in profs if p.name == "embeddings")
    assert emb.store_bytes == quantized_table_bytes(3000, 32 * 4)
    # 1 B/element + 4 B/row scale over 4 B/element dense
    assert emb.store_bytes / emb.nbytes == pytest.approx((32 + 4) / 128)
    assert emb.bytes_on(fast=True) == emb.nbytes
    assert emb.bytes_on(fast=False) == emb.store_bytes
    # fp32 profiles carry no quantized footprint (None -> dense)
    dense = gnn_recsys_profiles(1000, 2000, 30000, 32, 2)
    assert all(p.store_bytes is None for p in dense)

    topo = get_topology("dram-optane-appdirect")
    budgets = {"dram": 0, "optane": 1 << 40}         # force everything slow
    plan = place_greedy(profs, topo, budgets=budgets)
    assert plan.used["optane"] == sum(p.bytes_on(False) for p in profs)
    assert plan.used["optane"] < sum(p.nbytes for p in profs)


def test_quantized_tables_cost_less_off_fast():
    """Traffic pricing: an int8-stored table moves ~1/4 the bytes over
    the slow tier, so its demotion penalty drops accordingly — the
    byte-bandwidth argument the paper makes for every slow link."""
    topo = get_topology("dram-optane-appdirect")
    dense = AccessProfile("t", 1 << 20, reads_per_step=2.0,
                          writes_per_step=1.0, access_size=512)
    quant = dataclasses.replace(dense,
                                store_bytes=quantized_table_bytes(
                                    (1 << 20) // 512, 512))
    assert 0 < topo.demotion_penalty(quant) < \
        topo.demotion_penalty(dense) * 0.5
    # on-fast cost is storage-independent (tables compute in fp32 there)
    assert topo.step_time(quant, topo.fast) == \
        topo.step_time(dense, topo.fast)


def test_greedy_certified_by_exact_with_quantized_profiles():
    """Acceptance (4), certification half: with quantized store_bytes
    in the mix, pure greedy stays within 5% of the exact DP's optimal
    penalty on every registered topology, and per-tier budgets hold
    under quantized accounting."""
    for name in topology_names():
        topo = get_topology(name)
        for seed in range(4):
            rng = np.random.default_rng(seed)
            profs = []
            for i in range(10):
                nbytes = int(rng.integers(1, 10 ** 6))
                access = int(rng.choice([8, 64, 512, 4096]))
                store = quantized_table_bytes(max(nbytes // access, 1),
                                              access) \
                    if rng.random() < 0.5 else None
                profs.append(AccessProfile(
                    f"t{i}", nbytes,
                    reads_per_step=float(rng.uniform(0, 4)),
                    writes_per_step=float(rng.uniform(0, 4)),
                    access_size=access, store_bytes=store))
            total = sum(p.nbytes for p in profs)
            budgets = {topo.fast.name: max(total // 3, 1),
                       topo.slow.name: total + 1}
            greedy = place_greedy(profs, topo, budgets=budgets,
                                  exact_threshold=0)
            exact = place_exact(profs, topo, budgets=budgets)
            for plan in (greedy, exact):
                for t in topo.names:
                    assert plan.used[t] <= budgets[t], (name, seed, t)
            # penalties may be *negative* here: on the uniform topology
            # a quantized table is cheaper off-fast than on it, so slack
            # must scale with |penalty| to stay on the right side of 0
            g = greedy.est_step_penalty_s
            assert exact.est_step_penalty_s <= \
                g + abs(g) * 0.05 + 1e-18, (name, seed)


# ---------------------------------------------------------- trajectories
def test_default_compression_is_bit_identical():
    """Acceptance (1), single-device half: the default CompressionCfg()
    builds no compressor, adds no state, and trains bit-identically to
    an explicit exact run."""
    base = build(_smoke())
    assert base.pipeline.compressor is None
    assert set(base.state.keys()) == {"params", "opt"}
    explicit = build(_smoke(**{"compression.grads": "none"}))
    n = 5
    assert [base.step() for _ in range(n)] == \
        [explicit.step() for _ in range(n)]


def test_compressed_single_device_matches_exact_trajectory():
    """Acceptance (2), single-device half: int8 and topk+EF runs track
    the exact loss trajectory over 20 steps within fp32 tolerance, and
    the compressor state rides the training state."""
    exact = _losses(_smoke())
    int8 = build(_smoke(**{"compression.grads": "int8"}))
    assert set(int8.state.keys()) == {"params", "opt", "comp"}
    assert set(int8.state["comp"].keys()) == {"key", "ef"}
    l_int8 = [int8.step() for _ in range(20)]
    np.testing.assert_allclose(l_int8, exact, rtol=1e-3, atol=1e-4)

    l_topk = _losses(_smoke(**{"compression.grads": "topk",
                               "compression.frac": 0.1}))
    np.testing.assert_allclose(l_topk, exact, rtol=5e-3, atol=2e-3)
    # without error feedback top-k still converges but drifts more:
    # the residual carry is what keeps the trajectory centered
    l_noef = _losses(_smoke(**{"compression.grads": "topk",
                               "compression.frac": 0.1,
                               "compression.error_feedback": False}))
    np.testing.assert_allclose(l_noef, exact, rtol=2e-2, atol=5e-3)


def test_int8_embed_store_trains_to_same_tolerance():
    """Acceptance (3), training half: demoted tables stored int8
    (dequant-on-fetch, requantize-on-commit) train to the exact
    trajectory's tolerance; the identity default stays bit-identical."""
    tiered = {"memory.topology": "uniform",
              "memory.capacity": {"fast": 4096}}
    exact = _losses(_smoke(**tiered))
    q = build(_smoke(**{**tiered, "compression.embed_store": "int8"}))
    assert len(q.pipeline.plan.plan.demoted()) > 0
    l_q = [q.step() for _ in range(20)]
    # the tables really live quantized in the executor's store
    assert len(q.pipeline.executor._int8) == 2
    for name in q.pipeline.executor._int8:
        assert q.pipeline.executor.store_nbytes(name) < \
            q.state["params"][name.split("'")[1]].nbytes // 2
    np.testing.assert_allclose(l_q, exact, rtol=2e-2, atol=5e-3)
    # fp32 default on the same tight budget: still bit-identical
    fp32 = _losses(_smoke(**tiered), n=5)
    assert fp32 == exact[:5]


def test_recommender_serves_from_quantized_store():
    """Serving arm: a slow-tier table under embed_store='int8' sits
    behind the dequant-on-gather facade and scores within quantization
    tolerance of the fp32 snapshot."""
    from repro.eval import Recommender
    rng = np.random.default_rng(0)
    ue = rng.standard_normal((37, 16)).astype(np.float32)
    ie = rng.standard_normal((23, 16)).astype(np.float32)
    pins = {"serve/user_embed": "slow", "serve/item_embed": "slow"}
    fp32 = Recommender(ue, ie, k=5, user_batch=8, item_block=7,
                       topology="uniform", pins=pins)
    q = Recommender(ue, ie, k=5, user_batch=8, item_block=7,
                    topology="uniform", pins=pins, embed_store="int8")
    assert isinstance(q.user_e, QuantizedHostResident)
    assert isinstance(q.item_e, QuantizedHostResident)
    assert q.user_e.nbytes < ue.nbytes // 2
    _, scores_f = fp32.recommend(np.arange(37), exclude_seen=False)
    _, scores_q = q.recommend(np.arange(37), exclude_seen=False)
    # scores are inner products of ~unit rows: quantization moves each
    # row by <= scale ~ |row|_inf/127, so scores move by O(D * scale)
    np.testing.assert_allclose(scores_q, scores_f, atol=0.2)


# ---------------------------------------------------------- multi-device
def test_multidevice_compressed_parity_20_steps():
    """Acceptance (1) + (2), 4-device half: on the forced-4-device mesh
    the default config is bit-identical to the exact sharded run, and
    int8-psum / topk+EF runs track it over 20 steps."""
    out = run_with_devices("""
        import numpy as np
        from repro.api import Experiment, build
        ov = {"loop.steps": 20, "plan.target_batch": 64,
              "plan.microbatch": 4, "plan.warmup_epochs": 0,
              "data.edges": 1200, "loop.ckpt_dir": None,
              "mesh.shape": [4]}
        def run(extra):
            r = build(Experiment.from_preset(
                "lightgcn-smoke", {**ov, **extra}).spec)
            return r, [r.step() for _ in range(20)]
        r0, exact = run({})
        assert r0.pipeline.compressor is None
        _, default = run({"compression.grads": "none"})
        assert default == exact                      # bit-identical
        r8, int8 = run({"compression.grads": "int8"})
        assert r8.pipeline.compressor.shard is not None
        np.testing.assert_allclose(int8, exact, rtol=1e-3, atol=2e-4)
        _, topk = run({"compression.grads": "topk",
                       "compression.frac": 0.1})
        np.testing.assert_allclose(topk, exact, rtol=5e-3, atol=2e-3)
        # EF residual stacks are row-sharded over the dp axis
        import jax
        leaf = jax.tree.leaves(r8.state["comp"]["ef"])[0]
        assert leaf.shape[0] == 4
        assert "data" in str(leaf.sharding.spec)
        print("PARITY_OK")
    """, n=4)
    assert "PARITY_OK" in out


def test_multidevice_int8_combine_lowers_to_integer_allreduce():
    """The compressed combine is a *real* integer collective: the
    lowered HLO of the sharded int8 combine contains an all-reduce on
    s32 (int8 payload, int32 accumulate), which the exact fp32 combine
    does not."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.pipeline.shard import ShardPlan
        from repro.pipeline.compress import GradCompressor
        shard = ShardPlan((4,), ("data",))
        gc = GradCompressor("int8", shard=shard)
        grads = {"w": jnp.asarray(np.random.default_rng(0)
                 .standard_normal((64, 8)).astype(np.float32))}
        comp = gc.init_state(grads, seed=0)
        txt = jax.jit(gc).lower(grads, comp).compile().as_text()
        from repro.analysis.hlo_audit import assert_clean, expect
        assert_clean(txt, expect("grad-combine@int8"), where="int8-combine")
        # and the combine is faithful: sum of shares ~= the gradient
        combined, _ = jax.jit(gc)(grads, comp)
        np.testing.assert_allclose(np.asarray(combined["w"]),
                                   np.asarray(grads["w"]),
                                   rtol=0.2, atol=0.05)
        print("HLO_OK")
    """, n=4)
    assert "HLO_OK" in out


# ------------------------------------------------------ property tests
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    _HYP = True
except ImportError:                                    # pragma: no cover
    _HYP = False

if _HYP:
    SETTINGS = dict(max_examples=25, deadline=None)

    @pytest.mark.slow
    @given(n=st.integers(1, 400), seed=st.integers(0, 2 ** 16),
           mag=st.integers(-4, 4))
    @settings(**SETTINGS)
    def test_prop_quantize_roundtrip_bound(n, seed, mag):
        """|dequant(quant(g)) - g| <= scale per element, any magnitude
        (stochastic rounding moves at most one quantization step; the
        expected error is <= scale/2)."""
        rng = np.random.default_rng(seed)
        g = (rng.standard_normal(n) * 10.0 ** mag).astype(np.float32)
        q, scale = C.quantize_int8(jnp.asarray(g),
                                   jax.random.PRNGKey(seed))
        err = np.abs(np.asarray(C.dequantize_int8(q, scale)) - g)
        assert err.max() <= float(scale) * (1 + 1e-5)

    @pytest.mark.slow
    @given(rows=st.integers(1, 30), cols=st.integers(1, 24),
           k=st.integers(1, 64), seed=st.integers(0, 2 ** 16))
    @settings(**SETTINGS)
    def test_prop_topk_reconstruction_exact(rows, cols, k, seed):
        """sparsify -> densify reconstructs kept entries exactly and
        densify + residual == original, bitwise."""
        k = min(k, rows * cols)
        g = np.random.default_rng(seed).standard_normal((rows, cols)) \
            .astype(np.float32)
        kept, idx, residual = C.topk_sparsify(jnp.asarray(g), k)
        dense = np.asarray(C.topk_densify(kept, idx, g.shape))
        np.testing.assert_array_equal(dense + np.asarray(residual), g)
        np.testing.assert_array_equal(dense.reshape(-1)[np.asarray(idx)],
                                      np.asarray(kept))

    @pytest.mark.slow
    @given(n=st.integers(2, 60), frac=st.floats(0.05, 1.0),
           seed=st.integers(0, 2 ** 16))
    @settings(**SETTINGS)
    def test_prop_error_feedback_conserves_mass(n, frac, seed):
        """ErrorFeedback.apply residual-carry invariant under top-k:
        compressed + residual == grads + errors, exactly."""
        rng = np.random.default_rng(seed)
        grads = {"w": jnp.asarray(rng.standard_normal(n)
                                  .astype(np.float32))}
        errors = {"w": jnp.asarray(rng.standard_normal(n)
                                   .astype(np.float32))}
        g_hat, new_e = C.ErrorFeedback.apply(
            grads, errors, C.make_topk_compressor(frac))
        np.testing.assert_array_equal(
            np.asarray(g_hat["w"] + new_e["w"]),
            np.asarray(grads["w"] + errors["w"]))
