"""Runtime-layer tests: optimizers, compression, checkpoint/restart,
fault-tolerant loop, subgraph baseline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.optim import adafactor, adam, sgd
from repro.optim.compression import (ErrorFeedback, dequantize_int8,
                                     make_int8_compressor,
                                     make_topk_compressor, quantize_int8,
                                     topk_densify, topk_sparsify)
from repro.optim.optimizers import WarmupLinearLR, global_norm_clip
from repro.runtime.loop import LoopConfig, run_training


def quad_problem():
    target = jnp.asarray(np.random.default_rng(0).standard_normal((4, 8)),
                         jnp.float32)

    def loss(p):
        return jnp.mean((p["w"] - target) ** 2)

    params = {"w": jnp.zeros((4, 8))}
    return loss, params


@pytest.mark.parametrize("opt_name", ["sgd", "adam", "adafactor"])
def test_optimizers_converge(opt_name):
    loss, params = quad_problem()
    opt = {"sgd": sgd(5.0), "adam": adam(0.1),
           "adafactor": adafactor(0.3)}[opt_name]
    state = opt.init(params)
    l0 = float(loss(params))
    for _ in range(60):
        grads = jax.grad(loss)(params)
        params, state = opt.update(grads, state, params)
    assert float(loss(params)) < l0 * 0.05


def test_adafactor_bf16_grads():
    """adafactor must accept bf16 grads without materializing f32 copies
    (the API contract used by the 340B/1T train steps)."""
    loss, params = quad_problem()
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    opt = adafactor(0.3)
    state = opt.init(params)
    for _ in range(40):
        g = jax.grad(lambda p: loss(jax.tree.map(
            lambda x: x.astype(jnp.float32), p)))(params)
        params, state = opt.update(g, state, params)
    assert float(loss(jax.tree.map(lambda x: x.astype(jnp.float32),
                                   params))) < 0.5


def test_warmup_lr():
    fn = WarmupLinearLR(peak_lr=1.0, warmup_steps=10)
    assert float(fn(jnp.int32(5))) == pytest.approx(0.5)
    assert float(fn(jnp.int32(100))) == pytest.approx(1.0)


def test_global_norm_clip():
    g = {"a": jnp.full((10,), 3.0)}
    clipped, norm = global_norm_clip(g, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(90), rel=1e-5)
    n2 = jnp.sqrt(jnp.sum(clipped["a"] ** 2))
    assert float(n2) == pytest.approx(1.0, rel=1e-5)


# ---------------------------------------------------------------- compression
def test_int8_quantization_unbiased():
    rng = jax.random.PRNGKey(0)
    g = jax.random.normal(jax.random.PRNGKey(1), (1000,))
    acc = jnp.zeros_like(g)
    n = 30
    for i in range(n):
        q, s = quantize_int8(g, jax.random.fold_in(rng, i))
        acc = acc + dequantize_int8(q, s)
    np.testing.assert_allclose(acc / n, g, atol=0.02)


def test_topk_roundtrip():
    g = jnp.asarray(np.random.default_rng(2).standard_normal((64,)), jnp.float32)
    vals, idx, residual = topk_sparsify(g, 8)
    dense = topk_densify(vals, idx, g.shape)
    np.testing.assert_allclose(dense + residual, g, rtol=1e-6)
    assert (jnp.abs(dense[idx]) > 0).all()


def test_error_feedback_preserves_signal():
    """With error feedback, repeated compression of a constant gradient
    must transmit the full magnitude over time."""
    g = {"w": jnp.asarray(np.random.default_rng(3).standard_normal((128,)),
                          jnp.float32)}
    compress = make_topk_compressor(0.1)
    errors = ErrorFeedback.init(g)
    sent = jnp.zeros_like(g["w"])
    for _ in range(50):
        g_hat, errors = ErrorFeedback.apply(g, errors, compress)
        sent = sent + g_hat["w"]
    np.testing.assert_allclose(sent / 50, g["w"], atol=0.25)


def test_int8_compressor_error_feedback():
    g = {"w": jnp.linspace(-1, 1, 256)}
    compress = make_int8_compressor(jax.random.PRNGKey(0))
    errors = ErrorFeedback.init(g)
    g_hat, errors = ErrorFeedback.apply(g, errors, compress)
    np.testing.assert_allclose(g_hat["w"], g["w"], atol=0.02)


# ---------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(10), "b": [jnp.ones((3, 3)), jnp.zeros(2)],
            "t": jnp.int32(7)}
    save_checkpoint(str(tmp_path), 5, tree)
    assert latest_step(str(tmp_path)) == 5
    restored, step = restore_checkpoint(str(tmp_path), tree)
    assert step == 5
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(a, b)


def test_checkpoint_atomic_commit(tmp_path):
    tree = {"a": jnp.arange(4)}
    save_checkpoint(str(tmp_path), 1, tree)
    # a torn write (no COMMITTED marker) must be ignored
    os.makedirs(tmp_path / "step_2")
    with open(tmp_path / "step_2" / "manifest.json", "w") as f:
        f.write("{}")
    assert latest_step(str(tmp_path)) == 1


def test_checkpoint_async(tmp_path):
    tree = {"a": jnp.arange(100)}
    t = save_checkpoint(str(tmp_path), 3, tree, async_=True)
    t.join()
    restored, _ = restore_checkpoint(str(tmp_path), tree)
    np.testing.assert_array_equal(restored["a"], tree["a"])


def test_checkpoint_structure_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"a": jnp.arange(4)})
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), {"a": jnp.arange(4),
                                           "b": jnp.arange(2)})


# ---------------------------------------------------------------- loop
def test_loop_checkpoints_and_resumes(tmp_path):
    loss_fn, params = quad_problem()
    opt = sgd(0.2)

    def make_state():
        return {"params": params, "opt": opt.init(params)}

    def step_fn(state, step):
        grads = jax.grad(loss_fn)(state["params"])
        p, o = opt.update(grads, state["opt"], state["params"])
        return {"params": p, "opt": o}, loss_fn(p)

    cfg = LoopConfig(ckpt_dir=str(tmp_path), ckpt_every=5, max_steps=12,
                     async_ckpt=False)
    rep1 = run_training(cfg, make_state(), step_fn)
    assert rep1.steps_run == 12 and rep1.resumed_from is None
    # crash-restart: run again -> resumes from the final checkpoint
    cfg2 = LoopConfig(ckpt_dir=str(tmp_path), ckpt_every=5, max_steps=20,
                      async_ckpt=False)
    rep2 = run_training(cfg2, make_state(), step_fn)
    assert rep2.resumed_from == 12
    assert rep2.steps_run == 8
    assert rep2.losses[-1] < rep1.losses[0]


def test_loop_straggler_detection(tmp_path):
    import time
    calls = {"relayout": 0}

    def step_fn(state, step):
        time.sleep(0.02)
        return state, 0.0

    def on_relayout(state):
        calls["relayout"] += 1
        return state

    cfg = LoopConfig(ckpt_dir=str(tmp_path), ckpt_every=100, max_steps=8,
                     step_deadline_s=0.001, max_strays=3, async_ckpt=False)
    rep = run_training(cfg, {"x": jnp.zeros(1)}, step_fn, on_relayout)
    assert rep.relayout_requests >= 2
    assert calls["relayout"] >= 2


# ---------------------------------------------------------------- subgraph
def test_subgraph_trainer_step_and_redundancy():
    from repro.dist.subgraph import SubgraphTrainer
    rng = np.random.default_rng(0)
    n = 300
    src = rng.integers(0, n, 3000).astype(np.int32)
    dst = rng.integers(0, n, 3000).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((n, 8)).astype(np.float32))
    tr = SubgraphTrainer(src, dst, n, n_layers=2, fanout=5, n_workers=2)

    def loss_fn(emb, seeds):
        return jnp.mean(emb ** 2)

    seeds = rng.integers(0, n, 32).astype(np.int32)
    grads, stats = tr.step(seeds, x, loss_fn)
    assert grads.shape == x.shape
    assert stats.sample_s > 0 and stats.backward_s > 0
    assert stats.expanded_vertices > 32
    tr.step(seeds, x, loss_fn)  # overlapping batch
    assert tr.redundancy() > 1.0


def test_max_subgraph_batch_decreases_with_depth():
    from repro.dist.subgraph import max_subgraph_batch
    kw = dict(n_nodes_est_per_seed=1.0, embed_dim=128, mem_bytes=1e9,
              fanout=10, avg_degree=50)
    b1 = max_subgraph_batch(n_layers=1, **kw)
    b2 = max_subgraph_batch(n_layers=2, **kw)
    b3 = max_subgraph_batch(n_layers=3, **kw)
    assert b1 > b2 > b3  # paper Table 5: exponential shrink with depth


def test_compile_cache_dir_from_env_or_checkout(monkeypatch, tmp_path):
    """A JAX_COMPILATION_CACHE_DIR from the environment is left to JAX;
    unset, the cache goes to the fixed ``<checkout>/.jax_cache``."""
    import pathlib

    from repro.runtime.compile_cache import configure_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = configure_compile_cache()
        assert path == str(pathlib.Path(__file__).resolve().parents[1]
                           / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
