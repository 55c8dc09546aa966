"""Memory-tier subsystem tests (``repro.memory`` + the ``MemoryCfg``
spec surface): topology/policy registries, the pinned-penalty
accounting fix, greedy-vs-exact certification swept across every
registered topology, spec round-trips and CLI parity, and the tiered
executor's bit-identity contract (a host-demoted table trains and
serves bit-identically on the ``uniform`` topology)."""
import dataclasses

import numpy as np
import pytest

from repro.api import ExperimentSpec, MemoryCfg, build, get_preset
from repro.memory import (AccessProfile, HostResident, get_policy,
                          get_topology, gnn_recsys_profiles, place_exact,
                          place_greedy, policy_names, topology_names)


def _smoke(**overrides) -> ExperimentSpec:
    return get_preset("lightgcn-smoke").override(overrides)


# ------------------------------------------------------------- registries
def test_topology_and_policy_registries():
    assert {"tpu-hbm-host", "dram-optane-appdirect",
            "dram-optane-memorymode", "uniform"} <= set(topology_names())
    assert {"greedy", "exact", "paper-recipe",
            "all-fast", "all-slow"} <= set(policy_names())
    with pytest.raises(KeyError, match="unknown memory topology"):
        get_topology("nope")
    with pytest.raises(KeyError, match="unknown placement policy"):
        get_policy("nope")
    # passthrough: a live topology resolves to itself
    topo = get_topology("uniform")
    assert get_topology(topo) is topo


def test_tpu_preset_carries_legacy_constants():
    """The default preset's tiers hold exactly the values the old
    ``core.tiered_memory`` constants hardcoded, so legacy plans are
    numerically identical."""
    from repro.core import tiered_memory as tm
    topo = get_topology("tpu-hbm-host")
    assert topo.fast.read_bw == tm.HBM_BW_READ == 819e9
    assert topo.slow.read_bw == tm.HOST_BW_READ == 16e9
    assert topo.slow.write_bw == tm.HOST_BW_WRITE == 8e9
    assert topo.fast.capacity == tm.HBM_CAPACITY == 16 * 2**30
    assert topo.names == ("hbm", "host")
    assert not topo.is_uniform and get_topology("uniform").is_uniform


def test_uniform_topology_prices_demotion_at_zero():
    topo = get_topology("uniform")
    p = AccessProfile("t", 1 << 20, reads_per_step=3.0, writes_per_step=2.0,
                      access_size=8)
    assert topo.demotion_penalty(p) == 0.0
    assert get_topology("tpu-hbm-host").demotion_penalty(p) > 0.0


def test_capacity_override_validates_and_replaces():
    topo = get_topology("tpu-hbm-host").with_capacity({"hbm": 1 << 20})
    assert topo.tier("hbm").capacity == 1 << 20
    assert topo.tier("host").capacity == 512 * 2**30    # untouched
    with pytest.raises(KeyError):
        get_topology("uniform").with_capacity({"hbm": 1})


# ------------------------------------------------------------- policies
def test_pinned_slow_tier_counts_real_penalty():
    """The satellite fix: tensors pinned to the slow tier used to
    contribute 0.0 to est_step_penalty_s in both planners; they must
    report what the pin actually costs."""
    topo = get_topology("tpu-hbm-host")
    pinned = AccessProfile("pinned_t", 1000, reads_per_step=2.0,
                           writes_per_step=1.0, pinned="host")
    free = AccessProfile("free_t", 1000, reads_per_step=1.0)
    true_pen = topo.demotion_penalty(pinned)
    assert true_pen > 0.0
    for policy in (place_greedy, place_exact):
        plan = policy([pinned, free], topo,
                      budgets={"hbm": 4000, "host": 4000})
        assert plan.tier("pinned_t") == "host"
        assert plan.tier("free_t") == "hbm"
        assert plan.placements["pinned_t"].pinned
        assert plan.est_step_penalty_s == pytest.approx(true_pen, rel=1e-12)


def test_greedy_certified_by_exact_across_all_topologies():
    """Pure greedy (no exact fallback) must stay within 5% of the exact
    DP's optimal penalty on every registered topology — not just the
    default — and both must respect per-tier budgets."""
    for name in topology_names():
        topo = get_topology(name)
        for seed in range(4):
            rng = np.random.default_rng(seed)
            profs = [AccessProfile(
                f"t{i}", int(rng.integers(1, 10**6)),
                reads_per_step=float(rng.uniform(0, 4)),
                writes_per_step=float(rng.uniform(0, 4)),
                access_size=int(rng.choice([8, 64, 512, 4096])))
                for i in range(10)]
            total = sum(p.nbytes for p in profs)
            budgets = {topo.fast.name: max(total // 3, 1),
                       topo.slow.name: total + 1}
            greedy = place_greedy(profs, topo, budgets=budgets,
                                  exact_threshold=0)
            exact = place_exact(profs, topo, budgets=budgets)
            assert set(greedy.placements) == {p.name for p in profs}
            for plan in (greedy, exact):
                for t in topo.names:
                    assert plan.used[t] <= budgets[t]
            assert exact.est_step_penalty_s <= \
                greedy.est_step_penalty_s * 1.05 + 1e-18, (name, seed)


def test_greedy_on_uniform_keeps_fitting_tensors_fast():
    """Zero-penalty topologies must not demote gratuitously: among
    equal-penalty placements the planner (greedy AND its exact-DP
    fallback) keeps as many bytes as fit on the fast tier, so a
    uniform-topology run doesn't route every tensor through the host
    store for nothing."""
    topo = get_topology("uniform")
    profs = [AccessProfile(f"t{i}", 100) for i in range(5)]
    for kwargs in ({}, {"exact_threshold": 0}):     # DP path, pure greedy
        plan = place_greedy(profs, topo, budgets={"fast": 250, "slow": 500},
                            **kwargs)
        assert plan.used["fast"] == 200             # pow-of-fit: 2 of 5 x100
        assert plan.est_step_penalty_s == 0.0
    # and with room for everything, nothing is demoted at all
    roomy = place_greedy(profs, topo)
    assert roomy.demoted() == []


def test_paper_recipe_pins_follow_section6():
    profs = gnn_recsys_profiles(1000, 800, 20_000, 64, 2)
    topo = get_topology("dram-optane-appdirect")
    plan = get_policy("paper-recipe")(profs, topo)
    assert plan.tier("graph_coo") == "optane"
    assert plan.tier("opt_state") == "optane"
    assert plan.tier("messages_l0") == "optane"   # |E|-sized, nt-written
    assert plan.tier("embeddings") == "dram"
    assert plan.write_policy()["sddmm"] == "streaming"
    assert plan.policy == "paper-recipe"
    # the pins' real cost is visible (not the old 0.0)
    assert plan.est_step_penalty_s > 0.0
    # user pins override the recipe
    plan2 = get_policy("paper-recipe")(profs, topo,
                                       pins={"opt_state": "fast"})
    assert plan2.tier("opt_state") == "dram"


def test_all_fast_all_slow_baselines():
    profs = gnn_recsys_profiles(500, 400, 5_000, 32, 1)
    topo = get_topology("dram-optane-memorymode")
    fast = get_policy("all-fast")(profs, topo)
    slow = get_policy("all-slow")(profs, topo)
    assert fast.est_step_penalty_s == 0.0
    assert slow.est_step_penalty_s > 0.0
    assert all(p.tier == "dram-cache" for p in fast.placements.values())
    assert all(p.tier == "optane-mm" for p in slow.placements.values())


def test_write_policy_emitted_from_plan():
    profs = gnn_recsys_profiles(500, 400, 5_000, 32, 1)
    # write asymmetry to route around -> SDDMM streams (nt-write)
    tpu = get_policy("greedy")(profs, "tpu-hbm-host")
    assert tpu.write_policy() == {"sddmm": "streaming",
                                  "spmm": "accumulate",
                                  "embedding_bag": "accumulate"}
    # uniform topology, nothing demoted -> nothing to stream around
    uni = get_policy("all-fast")(profs, "uniform")
    assert uni.write_policy()["sddmm"] == "accumulate"
    # ... but a message stream demoted off the fast tier streams again
    pinned = get_policy("greedy")(profs, "uniform",
                                  pins={"messages_l0": "slow"})
    assert pinned.write_policy()["sddmm"] == "streaming"
    # the deprecated kernels.ops.WRITE_POLICY shim answers with the
    # default topology's table
    with pytest.warns(DeprecationWarning, match="emitted from the placement"):
        from repro.kernels import ops
        assert ops.WRITE_POLICY["sddmm"] == "streaming"


# ------------------------------------------------------------- MemoryCfg
def test_memorycfg_roundtrip_and_defaults():
    spec = _smoke(**{
        "memory.topology": "dram-optane-appdirect",
        "memory.policy": "paper-recipe",
        "memory.capacity": {"dram": 1 << 24},
        "memory.pins": {"params['item_embed']": "slow"}})
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    rt = ExperimentSpec.from_json(spec.to_json())
    assert rt.memory.capacity == {"dram": 1 << 24}
    assert rt.memory.pins == {"params['item_embed']": "slow"}
    # the default section is inert and equal across construction paths
    assert _smoke().memory == MemoryCfg()
    assert MemoryCfg().topology == "tpu-hbm-host"
    assert MemoryCfg().policy == "greedy"
    with pytest.raises(ValueError, match="unknown spec.memory keys"):
        ExperimentSpec.from_dict({"memory": {"topolgy": "uniform"}})


def test_memory_cli_flags_equal_spec_overrides():
    from repro.launch.train import build_arg_parser, spec_from_args
    args = build_arg_parser().parse_args([
        "--preset", "lightgcn-smoke", "--memory-topology", "uniform",
        "--placement-policy", "paper-recipe", "--pin", "item_embed=slow",
        "--pin", "graph=slow", "--ckpt-dir", "/tmp/ck"])
    spec = spec_from_args(args)
    expect = get_preset("lightgcn-smoke").override({
        "memory.topology": "uniform", "memory.policy": "paper-recipe",
        "memory.pins": {"item_embed": "slow", "graph": "slow"},
        "loop.ckpt_dir": "/tmp/ck/lightgcn"})
    assert spec == expect


def test_build_rejects_unknown_topology_and_policy():
    with pytest.raises(KeyError, match="unknown memory topology"):
        build(_smoke(**{"memory.topology": "pm-9000"}))
    with pytest.raises(KeyError, match="unknown placement policy"):
        build(_smoke(**{"memory.policy": "magic"}))


# ------------------------------------------------------------- acceptance
def test_section5_ordering_appdirect_beats_memorymode():
    """The paper's §5 qualitative result as a one-line spec change:
    the same paper-recipe plan costs less on AppDirect (explicit
    placement, nt-writes) than on Memory Mode (HW cache, normal
    writes, cacheline granularity)."""
    def penalty(topology):
        run = build(_smoke(**{"memory.topology": topology,
                              "memory.policy": "paper-recipe"}))
        plan = run.pipeline.plan.plan
        assert plan.policy == "paper-recipe"
        return plan.est_step_penalty_s

    p_ad = penalty("dram-optane-appdirect")
    p_mm = penalty("dram-optane-memorymode")
    assert 0.0 < p_ad < p_mm


def test_host_demoted_table_trains_bit_identical_on_uniform():
    """The tiered-gather parity acceptance test: pinning an embedding
    table to the slow tier routes it through the executor's host store
    (bytes live off-device, stream in per step) yet the uniform
    topology's run is bit-identical to the all-fast run."""
    n = 4
    base = build(_smoke(**{"memory.topology": "uniform"}))
    base_losses = [base.step() for _ in range(n)]

    demoted = build(_smoke(**{"memory.topology": "uniform",
                              "memory.pins": {"item_embed": "slow"}}))
    pipe = demoted.pipeline
    assert pipe.plan.plan.tier("params['item_embed']") == "slow"
    assert pipe.n_offloaded >= 1
    # the table's bytes genuinely live in the host store, not on device
    assert isinstance(demoted.state["params"]["item_embed"], np.ndarray)
    demoted_losses = [demoted.step() for _ in range(n)]

    assert demoted_losses == base_losses                 # bit-identical
    np.testing.assert_array_equal(
        np.asarray(demoted.params["item_embed"]),
        np.asarray(base.params["item_embed"]))
    np.testing.assert_array_equal(
        np.asarray(demoted.params["user_embed"]),
        np.asarray(base.params["user_embed"]))
    # ... and the default MemoryCfg() run matches too (uniform pricing
    # changes nothing on a backend whose tiers are all the same bytes)
    default = build(_smoke())
    assert [default.step() for _ in range(n)] == base_losses


def test_recommender_host_resident_serving_parity():
    """Serving through the row-granular HostResident facade (slow-tier
    tables, host bytes, per-batch gathers) returns bit-identical
    recommendations to the all-fast snapshot."""
    from repro.eval import Recommender
    rng = np.random.default_rng(0)
    ue = rng.standard_normal((37, 16)).astype(np.float32)
    ie = rng.standard_normal((23, 16)).astype(np.float32)

    fast = Recommender(ue, ie, k=5, user_batch=8, item_block=7,
                       topology="uniform")
    demoted = Recommender(ue, ie, k=5, user_batch=8, item_block=7,
                          topology="uniform",
                          pins={"serve/user_embed": "slow",
                                "serve/item_embed": "slow"})
    assert isinstance(demoted.user_e, HostResident)
    assert isinstance(demoted.item_e, HostResident)
    assert demoted.n_offloaded == 2
    ids_f, scores_f = fast.recommend(np.arange(37))
    ids_d, scores_d = demoted.recommend(np.arange(37))
    np.testing.assert_array_equal(ids_f, ids_d)
    np.testing.assert_array_equal(scores_f, scores_d)
    assert "topology=uniform" in demoted.describe()


def test_capacity_override_demotes_and_stays_bit_identical():
    """MemoryCfg.capacity drives real demotion (tight fast tier on the
    uniform topology) without changing the math."""
    spec_tight = _smoke(**{"memory.topology": "uniform",
                           "memory.capacity": {"fast": 4096}})
    tight = build(spec_tight)
    assert len(tight.pipeline.plan.plan.demoted()) > 0
    base = build(_smoke(**{"memory.topology": "uniform"}))
    n = 3
    assert [tight.step() for _ in range(n)] == \
        [base.step() for _ in range(n)]


def test_memory_kind_tier_trains_bit_identically():
    """On a backend that lists ``pinned_host`` the demoted table lives in
    host memory between steps; the jitted step moves it to the device
    and writes it back, and the losses equal the all-fast run's."""
    import jax

    from repro.memory import memory_kind_sharding
    if memory_kind_sharding("pinned_host") is None:
        pytest.skip("backend lists no pinned_host memory")
    base = _smoke(**{"plan.warmup_epochs": 0})
    fast = build(base).fit(steps=3).losses
    run = build(base.override({"memory.pins": {"item_embed": "slow"}}))
    assert run.pipeline.n_offloaded >= 1
    kind = lambda: run.state["params"]["item_embed"].sharding.memory_kind
    assert kind() == "pinned_host"
    assert run.fit(steps=3).losses == fast
    assert kind() == "pinned_host"
    # every step sees the same argument placements: one compile each
    assert run.pipeline._micro_value_and_grad._cache_size() == 1
    assert run.pipeline._apply_update._cache_size() == 1
    assert run.state["params"]["user_embed"].sharding.memory_kind == \
        jax.devices()[0].default_memory().kind


def test_memory_kind_sharding_only_for_listed_kinds():
    from repro.memory import memory_kind_sharding
    assert memory_kind_sharding(None) is None
    assert memory_kind_sharding("no-such-memory") is None
