"""Unified Experiment API tests: spec serialization round-trips, preset
parity with the ``repro.configs`` registry, CLI-flag -> spec-override
equivalence, and Run.fit() == a hand-built ``build_pipeline`` run
step for step (bit-identical losses) on the smoke config."""
import dataclasses
import json

import numpy as np
import pytest

from repro import configs as config_registry
from repro.api import (DataCfg, EvalCfg, Experiment, ExperimentSpec,
                       LoopCfg, MeshCfg, ModelCfg, PlanCfg, build,
                       get_preset, load_data, preset_names,
                       register_data_source)
from repro.pipeline import build_pipeline


def _smoke_spec(**overrides) -> ExperimentSpec:
    return get_preset("lightgcn-smoke").override(overrides)


# ------------------------------------------------------------- round trip
def test_spec_dict_roundtrip_exact():
    spec = ExperimentSpec(
        name="rt", model=ModelCfg(arch="ngcf", embed_dim=64, n_layers=3),
        data=DataCfg(source="kronecker", dataset="gowalla", edges=1000,
                     expand_factor=4, test_frac=0.2, seed=7),
        plan=PlanCfg(hbm_budget=1 << 20, target_batch=4096, microbatch=None,
                     base_batch=128, warmup_epochs=1, lr_scaling="sqrt"),
        loop=LoopCfg(steps=17, ckpt_dir="/tmp/x", eval_every=5),
        eval=EvalCfg(k=10, user_batch=64, item_block=256),
        optimizer="sgd", base_lr=0.05, l2=0.0, seed=3)
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec


def test_spec_json_file_roundtrip(tmp_path):
    spec = _smoke_spec()
    path = str(tmp_path / "spec.json")
    spec.save(path)
    assert ExperimentSpec.from_file(path) == spec
    # the file is plain JSON, editable by hand
    with open(path) as f:
        d = json.load(f)
    assert d["model"]["arch"] == "lightgcn"


def test_from_dict_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown spec keys"):
        ExperimentSpec.from_dict({"modle": {}})
    with pytest.raises(ValueError, match="unknown spec.model keys"):
        ExperimentSpec.from_dict({"model": {"embed_dims": 64}})


def test_override_dotted_paths():
    spec = _smoke_spec()
    out = spec.override({"model.embed_dim": 64, "plan.microbatch": 16},
                        optimizer="sgd")
    assert out.model.embed_dim == 64
    assert out.plan.microbatch == 16
    assert out.optimizer == "sgd"
    assert out.model.arch == spec.model.arch        # untouched fields kept
    with pytest.raises(KeyError):
        spec.override({"model.width": 64})


def test_model_hadamard_validates_and_threads_to_pipeline():
    """ModelCfg.hadamard round-trips, rejects unknown routes, and lands
    on PipelineConfig so the engine builds the requested NGCF dataflow."""
    with pytest.raises(ValueError, match="hadamard"):
        ModelCfg(hadamard="bogus")
    spec = _smoke_spec().override({"model.arch": "ngcf",
                                   "model.hadamard": "composed"})
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    assert spec.to_pipeline_config().hadamard == "composed"
    assert _smoke_spec().to_pipeline_config().hadamard == "auto"


# ------------------------------------------------------------- mesh section
def test_mesh_cfg_roundtrip_and_coercion():
    """MeshCfg survives the exact dict round-trip AND the JSON round-trip
    (JSON turns tuples into lists; __post_init__ coerces them back, so
    equality is structural, not representational)."""
    spec = _smoke_spec().override({"mesh.shape": (4,),
                                   "mesh.axes": ("data",),
                                   "mesh.spmm": "ring"})
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    rt = ExperimentSpec.from_json(spec.to_json())
    assert rt.mesh.shape == (4,) and isinstance(rt.mesh.shape, tuple)
    assert rt.mesh.axes == ("data",) and isinstance(rt.mesh.axes, tuple)
    # the default mesh is the inert single-device plan
    assert _smoke_spec().mesh == MeshCfg()
    assert MeshCfg().shape == (1,)


def test_mesh_cli_flags_equal_spec_overrides():
    from repro.launch.train import build_arg_parser, spec_from_args
    args = build_arg_parser().parse_args([
        "--preset", "lightgcn-smoke", "--mesh", "2x2",
        "--spmm", "ring", "--ckpt-dir", "/tmp/ck"])
    spec = spec_from_args(args)
    expect = get_preset("lightgcn-smoke").override(
        {"mesh.shape": (2, 2), "mesh.spmm": "ring",
         "loop.ckpt_dir": "/tmp/ck/lightgcn"})
    assert spec == expect


def test_mesh_single_device_spec_is_inert():
    """MeshCfg() (the default) must not change the engine's behavior at
    all: no ShardPlan is built and the pipeline config equals the
    pre-mesh projection field for field."""
    run = build(_smoke_spec())
    assert run.pipeline.shard is None
    cfg = _smoke_spec().to_pipeline_config()
    assert cfg.mesh_shape == (1,) and cfg.spmm is None


# ------------------------------------------------------------- presets
def test_preset_registry_absorbs_configs_full_and_smoke():
    """Every gnnrecsys config-registry entry must resolve to a preset
    whose model/data shapes match the registry declaration exactly."""
    found = 0
    for arch_id in config_registry.ARCH_IDS:
        mod = config_registry.get(arch_id)
        if getattr(mod, "FAMILY", None) != "gnnrecsys":
            continue
        for variant in ("full", "smoke"):
            cfg = getattr(mod, variant.upper())
            spec = get_preset(f"{arch_id}-{variant}")
            assert spec.name == cfg.name
            assert spec.model.arch == arch_id
            assert spec.model.embed_dim == cfg.embed_dim
            assert spec.model.n_layers == cfg.n_layers
            assert spec.data.n_users == cfg.n_users
            assert spec.data.n_items == cfg.n_items
            assert spec.data.edges == cfg.n_edges
            assert spec.plan.target_batch == cfg.bpr_batch
            assert spec.optimizer == mod.OPTIMIZER
            found += 1
    assert found >= 4                       # ngcf + lightgcn, full + smoke
    assert set(preset_names()) >= {"lightgcn-smoke", "lightgcn-full",
                                   "ngcf-smoke", "ngcf-full", "quickstart"}


def test_from_preset_smoke_trains():
    run = Experiment.from_preset("lightgcn-smoke").build()
    report = run.fit(steps=3)
    assert report.steps_run == 3
    assert all(np.isfinite(l) for l in report.losses)


# ------------------------------------------------------------- CLI parity
def test_cli_flags_equal_spec_overrides():
    from repro.launch.train import (build_arg_parser, default_spec,
                                    spec_from_args)
    args = build_arg_parser().parse_args([
        "--arch", "ngcf", "--embed-dim", "64", "--layers", "3",
        "--dataset", "gowalla", "--edges", "9000",
        "--target-batch", "4096", "--microbatch", "0",
        "--steps", "7", "--eval-every", "0", "--eval-k", "10"])
    via_cli = spec_from_args(args)
    via_api = default_spec().override({
        "model.arch": "ngcf", "model.embed_dim": 64, "model.n_layers": 3,
        "data.dataset": "gowalla", "data.edges": 9000,
        "plan.target_batch": 4096, "plan.microbatch": None,  # 0 -> derived
        "loop.steps": 7, "loop.eval_every": None,            # 0 -> off
        "loop.ckpt_dir": "/tmp/repro_ckpt/ngcf", "eval.k": 10})
    assert via_cli == via_api


def test_cli_set_and_preset_compose():
    from repro.launch.train import build_arg_parser, spec_from_args
    args = build_arg_parser().parse_args([
        "--preset", "lightgcn-smoke", "--set", "plan.hbm_budget=4096",
        "--set", "name=renamed", "--ckpt-dir", "/tmp/ck"])
    spec = spec_from_args(args)
    expect = get_preset("lightgcn-smoke").override(
        {"loop.ckpt_dir": "/tmp/ck/lightgcn", "plan.hbm_budget": 4096,
         "name": "renamed"})
    assert spec == expect


# ------------------------------------------------------------- data sources
def test_data_sources_one_protocol():
    tr, te = load_data(DataCfg(source="synth", dataset="gowalla",
                               edges=1000, test_frac=0.1))
    assert te is not None and tr.n_edges + te.n_edges == 1000
    tr, te = load_data(DataCfg(source="bipartite", n_users=40, n_items=30,
                               edges=300, test_frac=0.0))
    assert te is None and tr.n_users == 40 and tr.n_items == 30
    base = load_data(DataCfg(source="synth", dataset="movielens-10m",
                             edges=500, test_frac=0.0))[0]
    kron, _ = load_data(DataCfg(source="kronecker", dataset="movielens-10m",
                                edges=500, expand_factor=4, test_frac=0.0))
    assert kron.n_edges == 4 * base.n_edges
    assert kron.n_users > base.n_users


def test_register_custom_data_source():
    from repro.data.synth import InteractionData

    def tiny(cfg):
        u = np.arange(cfg.edges, dtype=np.int32) % 8
        i = np.arange(cfg.edges, dtype=np.int32) % 6
        return InteractionData(u, i, 8, 6)

    register_data_source("tiny-test", tiny)
    spec = _smoke_spec(**{"data.source": "tiny-test", "data.edges": 48,
                          "data.test_frac": 0.0, "plan.microbatch": 16,
                          "plan.target_batch": 16, "plan.base_batch": 16})
    run = build(spec)
    assert run.train_data.n_users == 8
    assert np.isfinite(run.step())


def test_unknown_data_source_raises():
    with pytest.raises(KeyError, match="unknown data source"):
        load_data(DataCfg(source="nope"))


# ------------------------------------------------------------- fit parity
def test_run_fit_matches_hand_built_pipeline_step_for_step():
    """Run.fit() through the API == a hand-built build_pipeline driven
    by step_fn directly: bit-identical losses on the smoke config, and
    a from_dict(to_dict()) round-tripped spec reproduces them again
    (the acceptance-criterion equivalence)."""
    spec = _smoke_spec()
    n = 6

    run = build(spec)
    api_losses = run.fit(steps=n).losses

    train, holdout = load_data(spec.data)
    pipe = build_pipeline(spec.to_pipeline_config(), train, holdout=holdout)
    state = pipe.init_state()
    hand_losses = []
    for s in range(n):
        state, loss = pipe.step_fn(state, s)
        hand_losses.append(float(loss))
    assert api_losses == hand_losses                    # bit-identical

    rt = Experiment.from_dict(spec.to_dict()).build()
    assert rt.fit(steps=n).losses == api_losses         # bit-identical

    for a, b in zip(np.asarray(run.params["user_embed"]).ravel(),
                    np.asarray(state["params"]["user_embed"]).ravel()):
        assert a == b


def test_fit_continues_in_memory_after_step_and_fit():
    """fit() on an in-memory run continues from the run's current
    position — step()/fit()/fit() == one straight fit of the same total
    length (schedule, sampling, and state all advance together)."""
    spec = _smoke_spec()
    inc = build(spec)
    losses = [inc.step()]
    rep1 = inc.fit(steps=2)
    rep2 = inc.fit(steps=3)
    assert rep1.steps_run == 2 and rep2.steps_run == 3
    losses += rep1.losses + rep2.losses
    assert inc.step_count == 6

    straight = build(spec)
    assert straight.fit(steps=6).losses == losses       # bit-identical
    np.testing.assert_array_equal(
        np.asarray(inc.params["user_embed"]),
        np.asarray(straight.params["user_embed"]))


def test_fit_checkpoint_resume_matches_uninterrupted(tmp_path):
    spec = _smoke_spec()
    ck = str(tmp_path / "ck")

    interrupted = build(spec)
    interrupted.fit(steps=4, ckpt_dir=ck)
    resumed = build(spec)
    rep = resumed.fit(steps=6, ckpt_dir=ck)     # restores step 4, runs 2
    assert rep.resumed_from == 4 and rep.steps_run == 2

    straight = build(spec)
    straight.fit(steps=6)                       # in-memory, no checkpoints
    np.testing.assert_array_equal(
        np.asarray(resumed.params["user_embed"]),
        np.asarray(straight.params["user_embed"]))

    # Run.resume positions a fresh run at the committed step exactly
    fresh = build(spec).resume(ck)
    assert fresh.step_count == 6
    assert fresh.step() == straight.step()      # same next loss, bit-exact


# ------------------------------------------------------------- eval/serving
def test_run_evaluate_and_recommend():
    spec = _smoke_spec(**{"loop.eval_every": 2})
    run = build(spec)
    report = run.fit(steps=4)
    assert [s for s, _ in report.eval_history] == [2, 4]
    m = run.evaluate()
    assert set(m) == {"recall@20", "ndcg@20", "mrr"}
    ids, scores = run.recommend([0, 1, 2], k=5)
    assert ids.shape == (3, 5) and scores.shape == (3, 5)
    # seen-item exclusion rides the train CSR
    indptr, items = run.pipeline.g.seen_csr()
    seen0 = set(items[indptr[0]:indptr[1]].tolist())
    assert seen0.isdisjoint(i for i in ids[0].tolist() if i >= 0)


def test_holdoutless_run_evaluate_raises():
    spec = _smoke_spec(**{"data.test_frac": 0.0})
    run = build(spec)
    assert run.holdout is None
    with pytest.raises(RuntimeError, match="no holdout"):
        run.evaluate()


# ------------------------------------------------------------- deprecation
def test_dense_mask_shim_warns_deprecation():
    from repro.core import bpr
    ue = np.eye(3, dtype=np.float32)
    ie = np.eye(3, dtype=np.float32)
    mask = np.zeros((3, 3), dtype=bool)
    test_pos = [np.array([0]), np.array([1]), np.array([2])]
    with pytest.warns(DeprecationWarning, match="repro.eval"):
        r = bpr.recall_at_k(ue, ie, mask, test_pos, k=1)
    assert r == 1.0
    # the canonical CSR path stays silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        bpr.recall_at_k(ue, ie, bpr.build_user_csr(
            np.array([0]), np.array([1]), 3), test_pos, k=1)
