"""Multi-device tests — run in a subprocess with fake CPU devices so the
main pytest process keeps its single-device jax config.  Host-side
pieces of the shard layer (edge bucketing, node partitioning) are
tested in-process."""
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def run_with_devices(code: str, n: int = 8):
    prog = f"import os\nos.environ['XLA_FLAGS'] = " \
           f"'--xla_force_host_platform_device_count={n}'\n" + \
           "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent(code)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=420, cwd=REPO)
    if res.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{res.stdout}\n{res.stderr}")
    return res.stdout


def test_ring_spmm_matches_dense():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.ring_spmm import bucket_edges, make_ring_spmm
        n_dev, n, d, e = 8, 64, 16, 400
        rng = np.random.default_rng(0)
        src = rng.integers(0, n, e).astype(np.int32)
        dst = rng.integers(0, n, e).astype(np.int32)
        x = rng.standard_normal((n, d)).astype(np.float32)
        src_l, indptr, per = bucket_edges(src, dst, n, n_dev)
        mesh = jax.make_mesh((n_dev,), ("data",))
        fn = make_ring_spmm(mesh, "data", per)
        with mesh:
            out = jax.jit(fn)(jnp.asarray(x), jnp.asarray(src_l),
                              jnp.asarray(indptr))
        a = np.zeros((n, n), np.float32)
        np.add.at(a, (dst, src), 1.0)
        np.testing.assert_allclose(np.asarray(out), a @ x, rtol=2e-4, atol=2e-4)
        print("RING_OK")
    """)
    assert "RING_OK" in out


def test_ring_spmm_uses_collective_permute():
    """The lowering must contain collective-permute (the overlap schedule),
    not all-gather of the full feature matrix."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.ring_spmm import bucket_edges, make_ring_spmm
        n_dev, n, d, e = 8, 64, 16, 200
        rng = np.random.default_rng(1)
        src = rng.integers(0, n, e).astype(np.int32)
        dst = rng.integers(0, n, e).astype(np.int32)
        x = rng.standard_normal((n, d)).astype(np.float32)
        src_l, indptr, per = bucket_edges(src, dst, n, n_dev)
        mesh = jax.make_mesh((n_dev,), ("data",))
        fn = make_ring_spmm(mesh, "data", per)
        with mesh:
            txt = jax.jit(fn).lower(jnp.asarray(x), jnp.asarray(src_l),
                jnp.asarray(indptr)).compile().as_text()
        from repro.analysis.hlo_audit import HloExpectation, assert_clean
        # the bare ring fn (unlike the full train step, where GSPMD
        # gathers embedding rows) must not all-gather the feature matrix
        assert_clean(txt, HloExpectation("ring-only",
                                         contains=("collective-permute",),
                                         absent=("all-gather",)),
                     where="ring-spmm")
        print("PERMUTE_OK")
    """)
    assert "PERMUTE_OK" in out


def test_compressed_psum_int8():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from repro.optim.compression import compressed_psum_int8
        n_dev = 8
        mesh = jax.make_mesh((n_dev,), ("data",))
        from jax.sharding import PartitionSpec as P
        g = np.random.default_rng(0).standard_normal((n_dev, 256)).astype(np.float32)
        def body(gs, key):
            return compressed_psum_int8(gs[0], key[0], "data")
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P("data", None), P("data")),
                           out_specs=P())
        keys = jax.random.split(jax.random.PRNGKey(0), n_dev)
        out = fn(jnp.asarray(g), keys)
        want = g.sum(0)
        err = np.abs(np.asarray(out) - want).max() / (np.abs(want).max() + 1e-9)
        assert err < 0.15, f"err {err}"
        print("PSUM_OK")
    """)
    assert "PSUM_OK" in out


def test_production_mesh_shapes():
    out = run_with_devices("""
        import jax
        from repro.launch.mesh import make_production_mesh, dp_axes, dp_size
        m1 = make_production_mesh()
        assert m1.axis_names == ("data", "model") and m1.devices.size == 256
        m2 = make_production_mesh(multi_pod=True)
        assert m2.axis_names == ("pod", "data", "model")
        assert m2.devices.size == 512
        assert dp_axes(m2) == ("pod", "data") and dp_size(m2) == 32
        print("MESH_OK")
    """, n=512)
    assert "MESH_OK" in out


@pytest.mark.parametrize("n,p,e", [
    (64, 8, 500),
    (64, 4, 333),
    (48, 4, 1),
    (16, 4, 0),                       # empty edge set
    (96, 2, 300),
])
def test_bucket_edges_emits_csr_buckets(n, p, e):
    """Each (dst device, ring step) bucket is a CSR over the device's
    local rows: row pointers from 0 to the bucket's edge count, padding
    past it, and exactly the edges a direct selection finds for the
    bucket, ordered by local dst row and, within a row, as given."""
    import sys as _sys
    _sys.path.insert(0, "src")
    from repro.dist.ring_spmm import bucket_edges
    rng = np.random.default_rng(42)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    src_l, indptr, n_local = bucket_edges(src, dst, n, p)
    assert n_local == n // p
    assert src_l.shape[:2] == indptr.shape[:2] == (p, p)
    assert indptr.shape[2] == n_local + 1
    emax = src_l.shape[2]
    assert emax % 8 == 0 and emax >= max(int(indptr[..., -1].max()), 1)
    rel = (src // n_local - dst // n_local) % p
    kept = 0
    for d in range(p):
        for k in range(p):
            ptr = indptr[d, k]
            assert ptr[0] == 0 and np.all(np.diff(ptr) >= 0)
            cnt = int(ptr[-1])
            sel = np.nonzero((dst // n_local == d) & (rel == k))[0]
            sel = sel[np.argsort(dst[sel] % n_local, kind="stable")]
            assert cnt == len(sel)
            kept += cnt
            rows = np.repeat(np.arange(n_local), np.diff(ptr))
            np.testing.assert_array_equal(rows, dst[sel] % n_local)
            np.testing.assert_array_equal(src_l[d, k, :cnt],
                                          src[sel] % n_local)
            assert not src_l[d, k, cnt:].any()
    assert kept == e                  # the full ring keeps every edge


def test_bucket_edges_rejects_ragged_and_shard_layer_pads():
    """bucket_edges keeps its divisibility contract; the shard layer's
    NodePartition is what absorbs ragged node counts."""
    import sys as _sys
    _sys.path.insert(0, "src")
    from repro.dist.ring_spmm import bucket_edges
    from repro.pipeline.shard import ShardPlan
    with pytest.raises(ValueError, match="not divisible"):
        bucket_edges(np.array([0]), np.array([1]), 10, 4)
    part = ShardPlan(shape=(4,)).partition(10)
    assert part.n_pad == 12 and part.n_local == 3
    # padded rows exist but own no edges
    src_l, indptr, n_local = bucket_edges(
        np.array([0, 9]), np.array([9, 0]), part.n_pad, 4)
    assert n_local == 3 and int(indptr[..., -1].sum()) == 2


def test_ring_dispatch_matches_csr_forward_and_grads():
    """BipartiteCSR ring dispatch vs the single-device CSR path on a
    RAGGED graph (n_users + n_items not divisible by P, so the shard
    layer pads and masks): sym_propagate and both directional
    aggregations must match in forward AND custom-VJP gradients.
    fp32 tolerance, not bit-identity: the ring sums each output row
    over P ring steps in rotation order, while the CSR kernel sums in
    one pass — a float32 reassociation."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.data import synth
        from repro.pipeline.shard import ShardPlan
        from repro.pipeline.sparse import BipartiteCSR
        data = synth.generate_bipartite(30, 23, 300, seed=3)   # N=53, P=4
        ref = BipartiteCSR(data.user, data.item, 30, 23)
        ring = BipartiteCSR(data.user, data.item, 30, 23,
                            shard=ShardPlan(shape=(4,), axes=("data",)))
        assert ring.spmm == "ring" and ring.shard.n_shards == 4
        rng = np.random.default_rng(0)
        xu = jnp.asarray(rng.standard_normal((30, 16)).astype(np.float32))
        xi = jnp.asarray(rng.standard_normal((23, 16)).astype(np.float32))
        for a, b in zip(ref.sym_propagate(xu, xi),
                        ring.sym_propagate(xu, xi)):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

        def loss(g):
            def f(xu, xi):
                hu, hi = g.sym_propagate(xu, xi)
                return (jnp.sum(hu ** 2) + jnp.sum(hi * xi)
                        + jnp.sum(g.agg_u2i(xu) ** 2)
                        + jnp.sum(g.agg_i2u(xi) ** 3))
            return f
        gr = jax.grad(loss(ref), argnums=(0, 1))(xu, xi)
        gs = jax.grad(loss(ring), argnums=(0, 1))(xu, xi)
        for a, b in zip(gr, gs):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
        print("RING_CSR_OK")
    """, n=4)
    assert "RING_CSR_OK" in out


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("which", ["sym", "ui", "iu"])
def test_ring_pallas_route_matches_dense(which, impl):
    """Each ring bucket aggregated by the gather-SpMM of ``impl`` (the
    Pallas kernel in interpret mode here, or the XLA oracle) inside the
    ring's shard_map: ``_RingGraph.matmul``'s forward equals the dense
    ``A @ x`` and its VJP ``A^T @ ct``, on a ragged graph (padded
    rows), for the symmetric op and each direction; ``ring_route``
    counts that route and no other."""
    out = run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro import obs
        from repro.data import synth
        from repro.pipeline.shard import ShardPlan
        from repro.pipeline.sparse import BipartiteCSR
        p, which, impl = 4, "{which}", "{impl}"
        nu, ni = 27, 18                       # N=45, padded to 48
        data = synth.generate_bipartite(nu, ni, 200, seed=11)
        g = BipartiteCSR(data.user, data.item, nu, ni, impl=impl,
                         shard=ShardPlan(shape=(p,)))
        ring = g._ring
        n = ring.part.n_pad
        u, i = data.user, data.item + nu
        s_all, d_all = {{"sym": (np.concatenate([u, i]),
                                 np.concatenate([i, u])),
                        "ui": (u, i), "iu": (i, u)}}[which]
        a = np.zeros((n, n), np.float32)
        np.add.at(a, (d_all, s_all), 1.0)
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((n, 8)).astype(np.float32))
        ct = jnp.asarray(rng.standard_normal((n, 8)).astype(np.float32))
        y, vjp = jax.vjp(lambda v: ring.matmul(which, v), x)
        np.testing.assert_allclose(y, a @ np.asarray(x), rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(vjp(ct)[0], a.T @ np.asarray(ct),
                                   rtol=2e-5, atol=2e-5)
        routes = obs.counters()["ring_route"]
        assert routes.get(impl, 0) > 0 and set(routes) == {{impl}}, routes
        print("RING_ROUTE_OK")
    """, n=4)
    assert "RING_ROUTE_OK" in out


def _removed_band():
    from repro.api import ExperimentSpec
    ExperimentSpec().override({"mesh.ring_steps": 2})


def _removed_int8_payload():
    from repro.api import ExperimentSpec
    d = ExperimentSpec().to_dict()
    d["compression"]["ring"] = "int8"
    ExperimentSpec.from_dict(d)


def _removed_impl_ring():
    from repro.pipeline.sparse import BipartiteCSR
    BipartiteCSR(np.array([0]), np.array([0]), 1, 1, impl="ring")


@pytest.mark.parametrize("build,err,match", [
    (_removed_band, KeyError, "unknown spec field 'ring_steps'"),
    (_removed_int8_payload, ValueError, r"compression keys \['ring'\]"),
    (_removed_impl_ring, ValueError, "spmm='ring'"),
], ids=["band", "int8-payload", "impl"])
def test_removed_ring_options_fail_loudly(build, err, match):
    """The ring has one schedule: a spec or graph that still asks for a
    banded ring, an int8 ring payload, or the ring through ``impl``
    raises, naming what it asked for, instead of running something
    else."""
    import sys as _sys
    _sys.path.insert(0, "src")
    with pytest.raises(err, match=match):
        build()


def test_sharded_fit_matches_single_device_trajectory():
    """The acceptance criterion: a MeshCfg(shape=(4,)) run through
    Run.fit() — ring-dispatched SpMM, dp-sharded batches, psum'd grads
    — must track the equivalent single-device run (same global batch:
    4 shards x microbatch 4 == microbatch 16) to fp32 tolerance, the
    lowered step must actually contain the ring collective-permute and
    the gradient all-reduce, and the sharded streaming eval must rank
    identically on identical embeddings."""
    out = run_with_devices("""
        import numpy as np
        from repro.api import build, get_preset
        from repro.eval import streaming_topk
        base = get_preset("lightgcn-smoke").override({
            "plan.microbatch": 16, "plan.target_batch": 64,
            "plan.base_batch": 64, "plan.warmup_epochs": 0})
        sharded = base.override({"mesh.shape": (4,), "mesh.axes": ("data",),
                                 "plan.microbatch": 4})
        r1 = build(base)
        l1 = r1.fit(steps=6).losses
        r2 = build(sharded)
        assert r2.pipeline.shard is not None
        assert r2.pipeline.plan.shards == 4
        assert r2.pipeline.plan.global_microbatch == 16
        # the Goyal rule must see the GLOBAL realized batch: same LR as
        # the single-device run, or the trajectories drift structurally
        assert r2.pipeline.lr_for_epoch(0) == r1.pipeline.lr_for_epoch(0)
        l2 = r2.fit(steps=6).losses
        # fp32 tolerance: ring summation + psum reassociate the fp32
        # reductions; the trajectories drift at float-noise scale
        np.testing.assert_allclose(l1, l2, rtol=5e-3, atol=1e-5)

        # lowered micro step: ring permute + psum'd grads
        pipe = r2.pipeline
        u, p, n = pipe._next_target_batch(1, 123)
        with pipe.step_context():
            db = pipe._device_batch(u[:16], p[:16], n[:16])
            txt = pipe._micro_value_and_grad.lower(
                r2.state["params"], pipe.g, *db).compile().as_text()
        from repro.analysis.hlo_audit import assert_clean, expectation_for
        assert_clean(txt, expectation_for(n_shards=4),
                     where="sharded-micro-step")

        # sharded streaming eval: identical embeddings -> identical
        # rankings (the dp-sharded sweep runs the same block merges)
        ue, ie = r2.embeddings()
        s0, i0 = streaming_topk(ue, ie, 10, user_batch=6)
        s1, i1 = streaming_topk(ue, ie, 10, user_batch=6,
                                shard=pipe.shard)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(s0, s1)
        m2 = r2.evaluate()
        assert np.isfinite(m2["recall@20"])
        print("SHARDED_FIT_OK")
    """, n=4)
    assert "SHARDED_FIT_OK" in out


def test_elastic_restore_to_different_mesh(tmp_path):
    """Checkpoint saved unsharded restores onto a different device count
    (elastic re-shard)."""
    out = run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
        save_checkpoint("{tmp_path}", 1, tree)
        mesh = jax.make_mesh((4,), ("data",))
        sh = {{"w": NamedSharding(mesh, P("data", None))}}
        restored, step = restore_checkpoint("{tmp_path}", tree,
                                            sharding_tree=sh)
        assert restored["w"].sharding.spec == P("data", None)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        print("ELASTIC_OK")
    """, n=4)
    assert "ELASTIC_OK" in out
