"""Chip smoke test: the main path, compiled, on one TPU (or four).

    python chip_smoke.py                 # one chip: kernels, training,
                                         # top-K serving, capacity tier
    python chip_smoke.py --chips 4       # four chips: the ring path only

One process drives every chip it uses, and nothing here starts another.
Phases (any failure raises, so the script exits non-zero):

  1. device check — JAX's first device must be a TPU;
  2. kernel parity — the compiled Pallas kernels (``spmm_csr`` in both
     modes, ``hadamard_spmm``, ``fused_topk_score``, ``ann_block_scores``)
     against their ``kernels/ref.py`` oracles at D=128, and the two
     serving kernels' f32 scores against a float64 host recompute (a
     bf16 matmul pass would fail it);
  3. training — the ``lightgcn-full`` preset (m-x25 widths: 349,184
     users, 53,248 items, D=128, 3 layers, its batch schedule) through
     ``Run.fit`` on the default TPU dispatch, with the edge count cut to
     ``--edges``; losses must be finite;
  4. serving — ``Run.recommend`` on a few hundred users through the fused
     top-K kernel: ids valid, unseen, and a top-K of a float64 host
     recompute, scores within f32 precision of it;
  5. capacity tier — two steps with ``item_embed`` pinned to the host
     tier (``pinned_host``); losses must equal the all-fast run's.

``--chips 4`` runs LightGCN with ``MeshCfg(shape=(4,))`` (ring SpMM,
psum'd gradients) against the single-device run with the same global
batch, in this process.

Earlier lines report figures (edge count, route, compile and step wall
times on the host clock around ``block_until_ready``, peak device
bytes).  The last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
# kernel-parity shapes: ragged on purpose (nothing divides its block)
PARITY = dict(n_dst=4_099, n_src=3_001, e=65_536, b=300, ni=5_000, nb=1_664)
# f32 scores against a float64 recompute, relative to |u| * max|i|: fp32
# passes land near 1e-7; one bf16 pass (TPU's default f32 matmul) near
# 1e-4
SCORE_RTOL = 1e-5


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_check(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: needs {chips} chips, JAX found {len(devs)}")
    log(f"device {devs[0].device_kind} x{len(devs)}")
    return devs


# ------------------------------------------------------------ kernels
def _csr(rng, n_dst, n_src, e):
    from repro.kernels.spmm import build_csr_by_dst
    dst = rng.integers(0, n_dst, e).astype(np.int32)
    src = rng.integers(0, n_src, e).astype(np.int32)
    return build_csr_by_dst(dst, src, n_dst)


def _bf16_exact(rng, shape):
    """f32 values exactly representable in bf16: every product is exact
    on the MXU whatever its pass count, so only summation order can
    differ from the oracle."""
    x = rng.standard_normal(shape).astype(np.float32)
    return np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))


def _rel_error(got, exact, ue, rows) -> float:
    """max |got - exact| relative to |u| * max|row|, for f32 scores of
    ``ue`` against ``rows`` and their float64 recompute ``exact``."""
    scale = np.linalg.norm(ue.astype(np.float64), axis=1)[:, None] \
        * np.linalg.norm(rows.astype(np.float64), axis=1).max()
    return float((np.abs(np.asarray(got, np.float64) - exact) / scale).max())


def serving_precision(rng, d: int) -> None:
    """The serving kernels score real f32 embeddings at f32 precision:
    the parity inputs above are bf16-exact, so they cannot show it."""
    from repro.kernels.ann import ann_block_scores_pallas
    from repro.kernels.topk_score import fused_topk_score_pallas

    b, ni, k = PARITY["b"], PARITY["ni"], 20
    ue = rng.standard_normal((b, d)).astype(np.float32)
    ie = rng.standard_normal((ni, d)).astype(np.float32)
    none = np.zeros((b, 1), np.int32)
    s, i = fused_topk_score_pallas(
        jnp.asarray(ue), jnp.asarray(ie), jnp.asarray(none),
        jnp.asarray(none, bool), k=k, item_block=1024, n_items=ni)
    exact = ue.astype(np.float64) @ ie.astype(np.float64).T
    err = _rel_error(s, np.take_along_axis(exact, np.asarray(i), 1), ue, ie)
    assert err <= SCORE_RTOL, err
    kth = np.sort(exact, axis=1)[:, -k]
    tol = SCORE_RTOL * np.linalg.norm(ue, axis=1) \
        * np.linalg.norm(ie, axis=1).max()
    assert (np.asarray(s)[:, -1] >= kth - tol).all(), "not a top-K"
    log(f"f32 scores fused_topk_score: max error {err:.3e} of |u|max|i| "
        f"(limit {SCORE_RTOL:g}): ok")

    nb = PARITY["nb"]
    cent = rng.integers(-127, 128, (nb, d)).astype(np.int8)
    sc = rng.uniform(1e-3, 0.1, nb).astype(np.float32)
    got = ann_block_scores_pallas(*map(jnp.asarray, (
        ue, cent, sc, np.zeros(nb, np.float32))))
    rows = cent.astype(np.float64) * sc[:, None]
    err = _rel_error(got, ue.astype(np.float64) @ rows.T, ue, rows)
    assert err <= SCORE_RTOL, err
    log(f"f32 scores ann_block_scores: max error {err:.3e} of |u|max|c| "
        f"(limit {SCORE_RTOL:g}): ok")


def kernel_parity() -> None:
    from repro.kernels import ref
    from repro.kernels.ann import ann_block_scores_pallas
    from repro.kernels.hadamard_spmm import hadamard_spmm_pallas
    from repro.kernels.spmm import spmm_csr_pallas
    from repro.kernels.topk_score import fused_topk_score_pallas

    rng = np.random.default_rng(0)
    d = 128
    n_dst, n_src, e = PARITY["n_dst"], PARITY["n_src"], PARITY["e"]
    indptr, src, perm = _csr(rng, n_dst, n_src, e)
    close = dict(rtol=1e-5, atol=1e-5)

    x = rng.standard_normal((n_src, d)).astype(np.float32)
    args = (jnp.asarray(x), jnp.asarray(indptr), jnp.asarray(src), n_dst)
    got = spmm_csr_pallas("sum", *args, gather=True)
    np.testing.assert_allclose(
        got, ref.spmm_csr_ref("sum", *args, gather=True), **close)
    log("parity spmm_csr gather: ok")

    vals = rng.standard_normal((e, d)).astype(np.float32)
    args = (jnp.asarray(vals), jnp.asarray(indptr), jnp.asarray(src), n_dst)
    np.testing.assert_allclose(spmm_csr_pallas("sum", *args),
                               ref.spmm_csr_ref("sum", *args), **close)
    log("parity spmm_csr edge-aggregation: ok")

    y = rng.standard_normal((n_dst, d)).astype(np.float32)
    y_idx = rng.integers(0, n_dst, e).astype(np.int32)
    scale = rng.standard_normal(n_dst).astype(np.float32)
    args = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(indptr),
            jnp.asarray(src), jnp.asarray(y_idx), n_dst)
    kw = dict(scale=jnp.asarray(scale), slope=0.2)
    np.testing.assert_allclose(hadamard_spmm_pallas(*args, **kw),
                               ref.hadamard_spmm_ref(*args, **kw), **close)
    log("parity hadamard_spmm: ok")

    # integer embeddings: many exact ties, so the (score desc, id asc)
    # contract is tested bit for bit; items not a multiple of the block
    b, ni, k, blk, seen_len = PARITY["b"], PARITY["ni"], 20, 1024, 64
    ue = rng.integers(-2, 3, (b, d)).astype(np.float32)
    ie = rng.integers(-2, 3, (ni, d)).astype(np.float32)
    seen = rng.integers(0, ni, (b, seen_len)).astype(np.int32)
    smask = rng.random((b, seen_len)) < 0.7
    args = (jnp.asarray(ue), jnp.asarray(ie), jnp.asarray(seen),
            jnp.asarray(smask))
    kw = dict(k=k, item_block=blk, n_items=ni)
    s_p, i_p = fused_topk_score_pallas(*args, **kw)
    s_r, i_r = ref.fused_topk_score_ref(*args, **kw)
    np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_r))
    np.testing.assert_array_equal(np.asarray(i_p), np.asarray(i_r))
    log("parity fused_topk_score: ok")

    nb = PARITY["nb"]
    ue = _bf16_exact(rng, (256, d))
    cq = rng.integers(-127, 128, (nb, d)).astype(np.int8)
    sc = rng.uniform(1e-3, 0.1, nb).astype(np.float32)
    rad = rng.uniform(0.0, 2.0, nb).astype(np.float32)
    args = tuple(map(jnp.asarray, (ue, cq, sc, rad)))
    np.testing.assert_allclose(ann_block_scores_pallas(*args),
                               ref.ann_block_scores_ref(*args), **close)
    log("parity ann_block_scores: ok")
    serving_precision(rng, d)


# ------------------------------------------------------------ training
def _spec(edges: int, **overrides):
    from repro.api import get_preset
    return get_preset("lightgcn-full").override({
        "data.edges": edges, "data.test_frac": 0.0, **overrides})


def _fit_timed(run, steps: int):
    """Run.fit one step at a time; wall seconds per step, each ended by
    block_until_ready on the state."""
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        report = run.fit(steps=1)
        jax.block_until_ready(run.state)
        secs.append(time.perf_counter() - t0)
        losses.extend(report.losses)
    return losses, secs


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_recommend(run, rng) -> None:
    """Serving through the fused top-K kernel: valid, unseen ids whose
    scores are a top-K of a float64 host recompute, within f32
    precision of it.  A user with fewer than k unseen items gets them
    all, then (-inf, -1) slots."""
    n_users = run.train_data.n_users
    indptr, items = run.pipeline.g.seen_csr()
    deg = np.diff(indptr)
    users = np.unique(np.concatenate([
        rng.choice(n_users, 299, replace=False), [int(deg.argmax())]]))
    k = run.spec.eval.k
    t0 = time.perf_counter()
    rec = run.recommender()
    ids, scores = rec.recommend(users)
    dt = time.perf_counter() - t0
    assert rec.impl == "pallas", rec.impl
    ue, ie = (np.asarray(a, np.float64) for a in run.embeddings())
    n_items = ie.shape[0]
    imax = np.linalg.norm(ie, axis=1).max()
    worst, short = 0.0, 0
    for row, u in enumerate(users):
        seen = items[indptr[u]:indptr[u + 1]]
        n_ok = min(k, n_items - len(seen))
        short += n_ok < k
        assert (ids[row][n_ok:] == -1).all(), ids[row]
        assert np.isneginf(scores[row][n_ok:]).all(), scores[row]
        got, s = ids[row][:n_ok], scores[row][:n_ok]
        assert ((got >= 0) & (got < n_items)).all(), got
        assert len(np.unique(got)) == n_ok
        assert not np.isin(got, seen).any(), "a seen item was recommended"
        assert (np.diff(s) <= 0).all(), "scores not descending"
        exact = ie @ ue[u]
        scale = np.linalg.norm(ue[u]) * imax
        err = np.abs(s - exact[got]).max() / scale
        assert err <= SCORE_RTOL, (u, err)
        worst = max(worst, err)
        exact[seen] = -np.inf
        kth = np.sort(exact)[-n_ok]
        assert s[-1] >= kth - SCORE_RTOL * scale, (s[-1], kth)
    log(f"recommend: {len(users)} users (max seen {int(deg[users].max())}, "
        f"{short} with fewer than k unseen) "
        f"k={k} route={rec.impl} fused, {dt:.3f}s wall incl. compile, "
        f"max score error {worst:.3e} of |u|max|i| (limit {SCORE_RTOL:g})"
        f": ok")


def one_chip(args, dev) -> None:
    from repro.api import build
    from repro.api.data import load_data

    t0 = time.perf_counter()
    kernel_parity()
    log(f"kernel parity: {time.perf_counter() - t0:.1f}s")

    spec = _spec(args.edges)
    t0 = time.perf_counter()
    train, _ = load_data(spec.data)
    log(f"graph: {train.n_users} users x {train.n_items} items, "
        f"{train.n_edges} edges (generated in "
        f"{time.perf_counter() - t0:.1f}s, host)")
    t0 = time.perf_counter()
    run = build(spec, train=train)
    log(f"build: {time.perf_counter() - t0:.1f}s")
    plan = run.pipeline.plan
    log(f"route: impl={plan.impl} spmm={run.pipeline.g.spmm} "
        f"microbatch={plan.microbatch} "
        f"accum={plan.microbatches_for_epoch(0)}x "
        f"target_batch={plan.sched.target_batch}")
    assert plan.impl == "pallas", plan.impl
    losses, secs = _fit_timed(run, args.steps)
    assert np.isfinite(losses).all(), losses
    log(f"train losses {losses}")
    log(f"train step wall s: first (compile + step) {secs[0]:.3f}, "
        f"then {[round(s, 3) for s in secs[1:]]}")
    log(f"peak_bytes_in_use after training: {_peak_bytes(dev)}")

    check_recommend(run, np.random.default_rng(1))
    log(f"peak_bytes_in_use after serving: {_peak_bytes(dev)}")
    del run

    pinned = build(_spec(args.edges,
                         **{"memory.pins": {"item_embed": "slow"}}),
                   train=train)
    table = pinned.state["params"]["item_embed"]
    assert table.sharding.memory_kind == "pinned_host", table.sharding
    p_losses, p_secs = _fit_timed(pinned, 2)
    table = pinned.state["params"]["item_embed"]
    assert table.sharding.memory_kind == "pinned_host", table.sharding
    log(f"capacity tier: {pinned.pipeline.executor.describe()} "
        f"losses {p_losses}, step wall s {[round(s, 3) for s in p_secs]}")
    assert p_losses == losses[:2], (p_losses, losses[:2])
    log("capacity tier losses equal the all-fast run's: ok")
    log(f"peak_bytes_in_use: {_peak_bytes(dev)}")


def four_chips(args, devs) -> None:
    """Ring SpMM + psum'd grads on a 4-chip mesh vs one chip, same
    global batch, same data."""
    from repro.api import build
    from repro.api.data import load_data

    base = _spec(args.edges)
    mb = base.plan.target_batch            # one accumulation chunk
    base = base.override({"plan.microbatch": mb})
    sharded = base.override({"mesh.shape": (4,), "mesh.axes": ("data",),
                             "plan.microbatch": mb // 4})
    train, _ = load_data(base.data)
    log(f"graph: {train.n_users} users x {train.n_items} items, "
        f"{train.n_edges} edges")
    r1 = build(base, train=train)
    l1, s1 = _fit_timed(r1, args.steps)
    log(f"single chip: route {r1.pipeline.plan.impl} losses {l1} "
        f"step wall s {[round(s, 3) for s in s1]}")
    r2 = build(sharded, train=train)
    assert r2.pipeline.g.spmm == "ring", r2.pipeline.g.spmm
    assert r2.pipeline.plan.global_microbatch == mb
    assert r2.pipeline.lr_for_epoch(0) == r1.pipeline.lr_for_epoch(0)
    l2, s2 = _fit_timed(r2, args.steps)
    log(f"4 chips ({r2.pipeline.shard.describe()}): losses {l2} "
        f"step wall s {[round(s, 3) for s in s2]}")
    # the tolerance of test_sharded_fit_matches_single_device_trajectory:
    # ring summation + psum reassociate the fp32 reductions
    np.testing.assert_allclose(l1, l2, rtol=5e-3, atol=1e-5)
    log("sharded trajectory matches single chip: ok")
    log(f"peak_bytes_in_use per chip: {[_peak_bytes(d) for d in devs[:4]]}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--edges", type=int, default=None,
                    help="edges cut from m-x25's 250,085,376 (default "
                         "2^24 on one chip, 2^20 for the four-chip check)")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()
    if args.edges is None:
        args.edges = 1 << 24 if args.chips == 1 else 1 << 20
    devs = device_check(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.runtime.compile_cache import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")
    if args.chips == 4:
        four_chips(args, devs)
    else:
        one_chip(args, devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
