"""Unified-pipeline tests: CSR forward equivalence vs the seed COO
models, gradient-accumulation == full-batch gradients, registry
round-trip, planner-placement propagation, and loop integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bpr, lightgcn, ngcf
from repro.core.graph import bipartite_from_numpy
from repro.data import synth
from repro.pipeline import (MODELS, BipartiteCSR, PipelineConfig,
                            build_pipeline, get_model)
from repro.runtime.loop import LoopConfig, run_pipeline


def _small():
    data = synth.generate_bipartite(60, 45, 600, seed=0)
    train, test = synth.train_test_split(data)
    return data, train, test


# ------------------------------------------------------- CSR equivalence
def test_lightgcn_csr_matches_coo():
    data, train, _ = _small()
    g_csr = BipartiteCSR(train.user, train.item, data.n_users, data.n_items)
    g_coo = bipartite_from_numpy(train.user, train.item, data.n_users,
                                 data.n_items)
    p = lightgcn.init_params(jax.random.PRNGKey(0), data.n_users,
                             data.n_items, 16)
    ue1, ie1 = get_model("lightgcn").forward(p, g_csr, 2)
    ue2, ie2 = lightgcn.forward(p, g_coo, n_layers=2)
    np.testing.assert_allclose(ue1, ue2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ie1, ie2, rtol=1e-5, atol=1e-6)


def test_ngcf_csr_matches_coo():
    data, train, _ = _small()
    g_csr = BipartiteCSR(train.user, train.item, data.n_users, data.n_items)
    g_coo = bipartite_from_numpy(train.user, train.item, data.n_users,
                                 data.n_items)
    p = ngcf.init_params(jax.random.PRNGKey(1), data.n_users, data.n_items,
                         16, 2)
    ue1, ie1 = get_model("ngcf").forward(p, g_csr, 2)
    ue2, ie2 = ngcf.forward(p, g_coo, opt_level=3)
    np.testing.assert_allclose(ue1, ue2, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(ie1, ie2, rtol=2e-4, atol=2e-5)


# --------------------------------------------------- fused Hadamard (NGCF)
def _ngcf_step_loss_and_grads(g, data, train, seed=0):
    """One full NGCF BPR train-step loss + grads through the registry."""
    p = ngcf.init_params(jax.random.PRNGKey(2), data.n_users, data.n_items,
                         16, 2)
    rng = np.random.default_rng(seed)
    b = 64
    pick = rng.integers(0, len(train.user), b)
    u = jnp.asarray(train.user[pick].astype(np.int32))
    pos = jnp.asarray(train.item[pick].astype(np.int32))
    neg = jnp.asarray(rng.integers(0, data.n_items, b).astype(np.int32))

    def loss_fn(p):
        ue, ie = get_model("ngcf").forward(p, g, 2)
        return bpr.bpr_loss(ue, ie, u, pos, neg)

    return jax.value_and_grad(loss_fn)(p)


def test_ngcf_fused_matches_composed():
    """The fused hadamard_spmm route (rematerializing VJP, no [E, D]
    message matrix) must reproduce the composed path's train-step loss
    and gradients within fp32 tolerance."""
    data, train, _ = _small()
    kw = dict(n_users=data.n_users, n_items=data.n_items)
    g_f = BipartiteCSR(train.user, train.item, hadamard="fused", **kw)
    g_c = BipartiteCSR(train.user, train.item, hadamard="composed", **kw)
    assert g_f.fused_hadamard and not g_c.fused_hadamard
    loss_f, grads_f = _ngcf_step_loss_and_grads(g_f, data, train)
    loss_c, grads_c = _ngcf_step_loss_and_grads(g_c, data, train)
    np.testing.assert_allclose(loss_f, loss_c, rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=2e-4, atol=1e-5), grads_f, grads_c)


def _collect_shapes(closed_jaxpr):
    """Every aval shape in a jaxpr, including all nested sub-jaxprs."""
    shapes = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    shapes.add(tuple(aval.shape))
            for val in eqn.params.values():
                items = val if isinstance(val, (list, tuple)) else [val]
                for item in items:
                    if hasattr(item, "jaxpr"):      # ClosedJaxpr
                        walk(item.jaxpr)
                    elif hasattr(item, "eqns"):     # raw Jaxpr
                        walk(item)

    walk(closed_jaxpr.jaxpr)
    return shapes


def test_fused_ngcf_jaxpr_has_no_edge_message():
    """Regression: the fused NGCF train step (Pallas dispatch) contains
    NO [E, D]-shaped intermediate anywhere in its jaxpr — forward,
    rematerializing backward, or optimizer — while the composed path
    provably does (so the scan itself is not vacuous)."""
    data, train, _ = _small()
    d = 16
    e = len(train.user)

    def shapes_for(hadamard, impl):
        g = BipartiteCSR(train.user, train.item, data.n_users, data.n_items,
                         impl=impl, hadamard=hadamard)
        p = ngcf.init_params(jax.random.PRNGKey(0), data.n_users,
                             data.n_items, d, 2)
        u = jnp.zeros(8, jnp.int32)

        def loss_fn(p):
            ue, ie = get_model("ngcf").forward(p, g, 2)
            return bpr.bpr_loss(ue, ie, u, u % data.n_items, u)

        jaxpr = jax.make_jaxpr(jax.value_and_grad(loss_fn))(p)
        return _collect_shapes(jaxpr)

    assert (e, d) in shapes_for("composed", "pallas")
    assert (e, d) not in shapes_for("fused", "pallas")


def test_bipartite_csr_hadamard_validation_and_ring_fallback():
    data, train, _ = _small()
    with pytest.raises(ValueError, match="hadamard"):
        BipartiteCSR(train.user, train.item, data.n_users, data.n_items,
                     hadamard="bogus")
    # the ring dispatch has no fused gather-multiply-aggregate: 'auto'
    # falls back to the composed route and the planner must see that
    g_ring = BipartiteCSR(train.user, train.item, data.n_users,
                          data.n_items, impl="ring")
    assert not g_ring.fused_hadamard
    assert get_model("ngcf").messages_materialized(g_ring)
    g = BipartiteCSR(train.user, train.item, data.n_users, data.n_items)
    assert not get_model("ngcf").messages_materialized(g)
    assert get_model("ngcf").materializes_messages    # static flag stands


def test_csr_custom_vjp_matches_autodiff():
    """The kernel-routed aggregation's custom VJP (reverse-direction SpMM)
    must match plain XLA autodiff of the same contraction."""
    data, train, _ = _small()
    g = BipartiteCSR(train.user, train.item, data.n_users, data.n_items)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (data.n_users, 8)).astype(np.float32))

    def via_kernel(x):
        return jnp.sum(g.agg_u2i(x) ** 2)

    def via_xla(x):
        m = x[g.ui_src]
        dst = g.ui_dst
        out = jax.ops.segment_sum(m, dst, num_segments=data.n_items)
        return jnp.sum(out ** 2)

    np.testing.assert_allclose(via_kernel(x), via_xla(x), rtol=1e-5)
    np.testing.assert_allclose(jax.grad(via_kernel)(x), jax.grad(via_xla)(x),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------------- grad accumulation
@pytest.mark.parametrize("batch", [128, 100])   # equal chunks + ragged tail
def test_grad_accumulation_matches_full_batch(batch):
    """Size-weighted accumulation of per-microbatch gradients == gradient
    of the full-batch mean loss (the acceptance-criterion equivalence),
    including when the batch is not a microbatch multiple."""
    data, train, _ = _small()
    cfg = PipelineConfig(arch="lightgcn", embed_dim=16, target_batch=128,
                         microbatch=32, base_batch=32)
    pipe = build_pipeline(cfg, train)
    params = pipe.init_state()["params"]
    rng = np.random.default_rng(0)
    u, i, n = bpr.sample_bpr_batch(rng, train.user, train.item,
                                   data.n_items, batch)

    _, acc_grads = pipe.grads_for_batch(params, u, i, n)

    def full_loss(p):
        ue, ie = pipe.spec.forward(p, pipe.g, cfg.n_layers)
        return bpr.bpr_loss(ue, ie, jnp.asarray(u), jnp.asarray(i),
                            jnp.asarray(n), l2=cfg.l2)

    full_grads = jax.grad(full_loss)(params)
    for a, b in zip(jax.tree.leaves(acc_grads), jax.tree.leaves(full_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_step_fn_accumulates_microbatches():
    """With target > microbatch the step must drain multiple loader
    microbatches (real accumulation), and the state must advance."""
    data, train, _ = _small()
    cfg = PipelineConfig(arch="lightgcn", embed_dim=8, target_batch=128,
                         microbatch=32, base_batch=128, warmup_epochs=0)
    pipe = build_pipeline(cfg, train)
    assert pipe.plan.microbatches_for_epoch(0) == 4
    state = pipe.init_state()
    before = pipe.loader.state.step
    state2, loss = pipe.step_fn(state, 0)
    consumed = pipe.loader.state.step - before
    assert consumed == 4 or pipe.loader.state.epoch > 0
    assert np.isfinite(loss)
    assert not np.allclose(np.asarray(state2["params"]["user_embed"]),
                           np.asarray(state["params"]["user_embed"]))


# ------------------------------------------------------- registry
@pytest.mark.parametrize("arch", sorted(MODELS))
def test_registry_roundtrip_trains(arch):
    data, train, _ = _small()
    cfg = PipelineConfig(arch=arch, embed_dim=8, target_batch=64,
                         microbatch=32, base_batch=32, warmup_epochs=0)
    pipe = build_pipeline(cfg, train)
    state = pipe.init_state()
    losses = []
    for s in range(3):
        state, loss = pipe.step_fn(state, s)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    ue, ie = pipe.embeddings(state)
    assert ue.shape[0] == data.n_users and ie.shape[0] == data.n_items
    assert bool(jnp.isfinite(ue).all()) and bool(jnp.isfinite(ie).all())


# ------------------------------------------------------- planner threading
def test_planner_placements_cover_state_and_graph():
    data, train, _ = _small()
    cfg = PipelineConfig(arch="lightgcn", embed_dim=16, target_batch=64,
                         microbatch=32)
    pipe = build_pipeline(cfg, train)
    names = set(pipe.plan.plan.placements)
    leaf_names = {"params" + jax.tree_util.keystr(kp) for kp, _ in
                  jax.tree_util.tree_flatten_with_path(
                      pipe.init_state()["params"])[0]}
    assert leaf_names <= names
    assert "graph/csr" in names


def test_tight_budget_demotes_to_host_and_shrinks_microbatch():
    """A tight HBM budget must (a) demote some tensors to the host tier
    and (b) propagate into a smaller derived microbatch."""
    data, train, _ = _small()
    total = None
    cfg_big = PipelineConfig(arch="ngcf", embed_dim=32, target_batch=2048)
    big = build_pipeline(cfg_big, train)
    total = big.plan.plan.hbm_used
    cfg_tight = PipelineConfig(arch="ngcf", embed_dim=32, target_batch=2048,
                               hbm_budget=max(total // 3, 4096))
    tight = build_pipeline(cfg_tight, train)
    tiers = {p.tier for p in tight.plan.plan.placements.values()}
    assert "host" in tiers
    assert tight.plan.plan.est_step_penalty_s > 0
    assert tight.plan.microbatch <= big.plan.microbatch


def test_relayout_replans_over_current_state():
    data, train, _ = _small()
    cfg = PipelineConfig(arch="lightgcn", embed_dim=8, target_batch=64,
                         microbatch=32)
    pipe = build_pipeline(cfg, train)
    state = pipe.init_state()
    old_plan = pipe.plan
    state = pipe.on_relayout(state)
    assert pipe.plan is not old_plan
    assert set(pipe.plan.plan.placements) == set(old_plan.plan.placements)


# ------------------------------------------------------- loop integration
def test_run_pipeline_checkpoints_and_resumes(tmp_path):
    data, train, _ = _small()
    cfg = PipelineConfig(arch="lightgcn", embed_dim=8, target_batch=64,
                         microbatch=32, base_batch=32, warmup_epochs=0)
    pipe = build_pipeline(cfg, train)
    rep1 = run_pipeline(LoopConfig(ckpt_dir=str(tmp_path), ckpt_every=2,
                                   max_steps=4, async_ckpt=False), pipe)
    assert rep1.steps_run == 4 and rep1.resumed_from is None
    pipe2 = build_pipeline(cfg, train)
    rep2 = run_pipeline(LoopConfig(ckpt_dir=str(tmp_path), ckpt_every=2,
                                   max_steps=6, async_ckpt=False), pipe2)
    assert rep2.resumed_from == 4 and rep2.steps_run == 2
    # the resumed loader continued mid-schedule instead of restarting:
    # it must sit where 6 uninterrupted steps would leave it
    ref = build_pipeline(cfg, train)
    ref.seek(6)
    assert pipe2.loader.state == ref.loader.state


def test_seek_matches_live_progression():
    """seek(n) must land the loader exactly where n live steps leave it —
    the contract that makes checkpoint resume schedule-exact."""
    data, train, _ = _small()
    cfg = PipelineConfig(arch="lightgcn", embed_dim=8, target_batch=128,
                         microbatch=32, base_batch=32, warmup_epochs=1)
    live = build_pipeline(cfg, train)
    state = live.init_state()
    for s in range(5):
        state, _ = live.step_fn(state, s)
    seeked = build_pipeline(cfg, train)
    seeked.seek(5)
    assert seeked.loader.state == live.loader.state
    # and the next batch drawn by each is identical
    k = live.plan.microbatches_for_epoch(live.loader.state.epoch)
    u1, p1, n1 = live._next_target_batch(k, 5)
    u2, p2, n2 = seeked._next_target_batch(k, 5)
    np.testing.assert_array_equal(u1, u2)
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_array_equal(n1, n2)


def test_graph_is_a_step_argument_not_a_constant():
    """The jitted micro step takes the CSR graph as a pytree argument:
    its lowered program does not grow with the edge count (closed-over
    index arrays would be inlined as constants)."""
    def lowered_size(edges):
        data = synth.generate_bipartite(40, 30, edges, seed=0)
        pipe = build_pipeline(PipelineConfig(embed_dim=8, microbatch=16,
                                             target_batch=16), data)
        leaves = jax.tree.leaves(pipe.g)
        assert any(leaf.shape == (data.n_edges,) for leaf in leaves)
        u, p, n = pipe._next_target_batch(1, 0)
        return len(pipe._micro_value_and_grad.lower(
            pipe.init_state()["params"], pipe.g,
            *pipe._device_batch(u, p, n)).as_text())

    small, large = lowered_size(100), lowered_size(1000)
    assert large < small * 1.05, (small, large)


@pytest.mark.parametrize("arch", sorted(MODELS))
def test_ring_ops_are_built_before_tracing(arch):
    """Under the ring dispatch the pipeline builds every ring op its
    model's forward and VJP apply (ModelSpec.ring_ops) before the first
    trace: lowering the micro step needs no bucketing inside the trace.
    A graph argument missing an op raises rather than baking
    host-bucketed cubes into the program."""
    data, train, _ = _small()
    pipe = build_pipeline(PipelineConfig(arch=arch, embed_dim=8,
                                         microbatch=16, target_batch=16,
                                         impl="ring"), train)
    assert pipe.g.spmm == "ring"
    u, p, n = pipe._next_target_batch(1, 0)
    pipe._micro_value_and_grad.lower(pipe.init_state()["params"], pipe.g,
                                     *pipe._device_batch(u, p, n))

    g = BipartiteCSR(train.user, train.item, data.n_users, data.n_items,
                     impl="ring")
    x = jnp.ones((data.n_users, 8), jnp.float32)
    u2i = jax.jit(lambda g, x: g.agg_u2i(x))
    with pytest.raises(RuntimeError, match="before tracing"):
        u2i(g, x)
    g.build_ring(("ui", "iu"))
    np.testing.assert_allclose(u2i(g, x), g.agg_u2i(x))
