"""Kernel parity on adversarial shapes + gradient checks (slow tier).

Pallas kernels (interpret mode) vs the ``kernels/ref.py`` oracles on the
shapes that break naive tilings: empty destination rows, edge counts
that are not a multiple of the edge block, feature widths that are not a
multiple of 128 (the TPU lane width), row/bag counts that don't divide
their block.  Plus finite-difference checks of the custom-VJP SpMM ops
in ``pipeline/sparse.py`` on both dispatch paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.sddmm import sddmm_pallas
from repro.kernels.spmm import EDGE_CHUNK, build_csr_by_dst, spmm_csr_pallas
from repro.pipeline.sparse import BipartiteCSR

pytestmark = pytest.mark.slow


# ------------------------------------------------------------------- spmm
@pytest.mark.parametrize("reduce", ["sum", "max"])
@pytest.mark.parametrize("gather", [False, True])
@pytest.mark.parametrize("n,e,d,rb", [
    (9, 30, 100, 4),     # D not a multiple of 128, n % row_block != 0
    (13, 21, 37, 8),     # everything ragged
    (6, 12, 130, 4),     # D just over one lane tile
    (5, 1, 8, 4),        # single edge
])
def test_spmm_adversarial_shapes(reduce, gather, n, e, d, rb):
    rng = np.random.default_rng(hash((reduce, gather, n, e, d)) % 2**31)
    src = rng.integers(0, n, e).astype(np.int32)
    # adversarial: all edges land on a strict subset of rows, so several
    # destination rows are empty (the -inf -> 0 path for 'max')
    dst = rng.integers(0, max(n // 2, 1), e).astype(np.int32)
    indptr, src_sorted, perm = build_csr_by_dst(dst, src, n)
    if gather:
        values = rng.standard_normal((n, d)).astype(np.float32)
    else:
        values = rng.standard_normal((e, d)).astype(np.float32)[perm]
    got = spmm_csr_pallas(reduce, jnp.asarray(values), jnp.asarray(indptr),
                          jnp.asarray(src_sorted), n, row_block=rb,
                          gather=gather)
    want = ref.spmm_csr_ref(reduce, jnp.asarray(values), jnp.asarray(indptr),
                            jnp.asarray(src_sorted), n, gather=gather)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # empty rows really exist and are exactly zero in both
    empty = np.diff(indptr) == 0
    assert empty.any()
    np.testing.assert_array_equal(np.asarray(got)[empty], 0.0)


@pytest.mark.parametrize("reduce", ["sum", "max"])
@pytest.mark.parametrize("gather", [False, True])
def test_spmm_smem_window_refills(reduce, gather):
    """Row pointers and source indices stream through SMEM windows of
    EDGE_CHUNK entries; with more rows and edges than one window holds,
    both windows refill (straddling row and block boundaries) and must
    match the oracle exactly as the one-window case does."""
    rng = np.random.default_rng(5)
    n, e, d = EDGE_CHUNK + 300, 3 * EDGE_CHUNK + 77, 8
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    indptr, src_sorted, perm = build_csr_by_dst(dst, src, n)
    values = rng.standard_normal((n, d) if gather else (e, d))
    values = values.astype(np.float32) if gather \
        else values.astype(np.float32)[perm]
    args = (jnp.asarray(values), jnp.asarray(indptr),
            jnp.asarray(src_sorted), n)
    got = spmm_csr_pallas(reduce, *args, gather=gather)
    want = ref.spmm_csr_ref(reduce, *args, gather=gather)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ sddmm
@pytest.mark.parametrize("op", ["mul", "add", "dot", "copy"])
@pytest.mark.parametrize("n,e,d,eb", [
    (7, 13, 100, 8),     # E % edge_block != 0, D % 128 != 0
    (5, 1, 37, 16),      # single edge, block > E
    (11, 33, 130, 16),   # D just over one lane tile
])
def test_sddmm_adversarial_shapes(op, n, e, d, eb):
    rng = np.random.default_rng(hash((op, n, e, d)) % 2**31)
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.standard_normal((n, d)).astype(np.float32)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    mask = rng.random(e) > 0.3
    coeff = rng.standard_normal(e).astype(np.float32) if op == "copy" else None
    args = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(src),
            jnp.asarray(dst), jnp.asarray(mask),
            None if coeff is None else jnp.asarray(coeff))
    got = sddmm_pallas(op, *args, edge_block=eb)
    want = ref.sddmm_ref(op, *args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------- embedding bag
@pytest.mark.parametrize("combiner", ["sum", "mean"])
@pytest.mark.parametrize("v,b,l,d,bb", [
    (17, 5, 3, 100, 4),   # B % bag_block != 0, D % 128 != 0
    (9, 1, 4, 37, 8),     # single bag
    (33, 7, 2, 130, 4),
])
def test_embedding_bag_adversarial_shapes(combiner, v, b, l, d, bb):
    rng = np.random.default_rng(hash((combiner, v, b, l, d)) % 2**31)
    table = rng.standard_normal((v, d)).astype(np.float32)
    ids = rng.integers(0, v, (b, l)).astype(np.int32)
    mask = rng.random((b, l)) > 0.4
    mask[0, :] = False                       # a fully-empty bag
    got = embedding_bag_pallas(jnp.asarray(table), jnp.asarray(ids),
                               jnp.asarray(mask), combiner, bag_block=bb)
    want = ref.embedding_bag_ref(jnp.asarray(table), jnp.asarray(ids),
                                 jnp.asarray(mask), combiner)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got)[0], 0.0)  # empty bag -> 0


# ------------------------------------------- custom-VJP SpMM grad checks
def _fd_check(loss, x, probes, eps=1e-2, rtol=2e-2):
    """Central finite differences along a few unit probes vs autodiff."""
    g = jax.grad(loss)(x)
    for idx in probes:
        probe = jnp.zeros_like(x).at[idx].set(1.0)
        fd = (loss(x + eps * probe) - loss(x - eps * probe)) / (2 * eps)
        np.testing.assert_allclose(np.asarray(g)[idx], fd, rtol=rtol,
                                   atol=1e-3)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_adj_matmul_custom_vjp_finite_difference(impl):
    """d/dx sum(f(A x)) via the custom VJP (reverse-direction SpMM) must
    match central finite differences on both dispatch paths."""
    rng = np.random.default_rng(0)
    nu, ni, e, d = 8, 6, 18, 4
    user = rng.integers(0, nu, e).astype(np.int32)
    item = rng.integers(0, ni, e).astype(np.int32)
    g = BipartiteCSR(user, item, nu, ni, impl=impl)
    x = jnp.asarray(rng.standard_normal((nu, d)).astype(np.float32))

    def loss(x):
        return jnp.sum(g.agg_u2i(x) ** 2) + jnp.sum(g.agg_i2u(g.agg_u2i(x)))

    _fd_check(loss, x, [(0, 0), (3, 2), (7, 3)])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_edge_agg_custom_vjp_finite_difference(impl):
    """d/dvalues of the edge aggregation (SDDMM-copy gather VJP)."""
    rng = np.random.default_rng(1)
    nu, ni, e, d = 6, 7, 15, 3
    user = rng.integers(0, nu, e).astype(np.int32)
    item = rng.integers(0, ni, e).astype(np.int32)
    g = BipartiteCSR(user, item, nu, ni, impl=impl)
    values = jnp.asarray(rng.standard_normal((e, d)).astype(np.float32))

    def loss(v):
        return jnp.sum(jnp.tanh(g.edge_agg_item(v)))

    _fd_check(loss, values, [(0, 0), (7, 1), (14, 2)])


def test_custom_vjp_matches_plain_autodiff_of_ref():
    """The hand-written VJP equals XLA autodiff of the reference SpMM
    contraction (the paper's grad-is-the-reverse-SpMM identity)."""
    rng = np.random.default_rng(2)
    nu, ni, e, d = 10, 9, 30, 5
    user = rng.integers(0, nu, e).astype(np.int32)
    item = rng.integers(0, ni, e).astype(np.int32)
    g = BipartiteCSR(user, item, nu, ni, impl="xla")
    x = jnp.asarray(rng.standard_normal((nu, d)).astype(np.float32))
    a = np.zeros((ni, nu), np.float32)
    np.add.at(a, (item, user), 1.0)
    a = jnp.asarray(a)

    def via_custom(x):
        return jnp.sum(jnp.sin(g.agg_u2i(x)))

    def via_dense(x):
        return jnp.sum(jnp.sin(a @ x))

    np.testing.assert_allclose(jax.grad(via_custom)(x),
                               jax.grad(via_dense)(x), rtol=1e-4, atol=1e-5)


# ------------------------------------------------------ hadamard_spmm
def _hadamard_case(seed, n_src, n_dst, e, integer=False):
    """dst-sorted CSR + per-edge (x_idx, y_idx) gather indices; edges
    land on a strict subset of destinations so empty rows exist."""
    rng = np.random.default_rng(seed)
    dst = np.sort(rng.integers(0, max(n_dst // 2, 1), e)).astype(np.int32)
    indptr = np.searchsorted(dst, np.arange(n_dst + 1)).astype(np.int32)
    x_idx = rng.integers(0, n_src, e).astype(np.int32)
    y_idx = rng.integers(0, n_dst, e).astype(np.int32)

    def feats(n, d):
        if integer:
            return rng.integers(-3, 4, (n, d)).astype(np.float32)
        return rng.standard_normal((n, d)).astype(np.float32)

    return indptr, x_idx, y_idx, dst, feats


@pytest.mark.parametrize("n_src,n_dst,e,d,rb", [
    (9, 7, 30, 100, 4),    # D % 128 != 0, n_dst % row_block != 0
    (13, 11, 21, 37, 8),   # everything ragged
    (6, 5, 1, 130, 4),     # single edge, D just over one lane tile
    (8, 6, 0, 16, 4),      # zero edges: all rows empty
])
def test_hadamard_spmm_adversarial_shapes(n_src, n_dst, e, d, rb):
    from repro.kernels.hadamard_spmm import hadamard_spmm_pallas
    indptr, x_idx, y_idx, _, feats = _hadamard_case(
        hash((n_src, n_dst, e, d)) % 2**31, n_src, n_dst, e)
    x, y = feats(n_src, d), feats(n_dst, d)
    got = hadamard_spmm_pallas(jnp.asarray(x), jnp.asarray(y),
                               jnp.asarray(indptr), jnp.asarray(x_idx),
                               jnp.asarray(y_idx), n_dst, row_block=rb)
    want = ref.hadamard_spmm_ref(jnp.asarray(x), jnp.asarray(y),
                                 jnp.asarray(indptr), jnp.asarray(x_idx),
                                 jnp.asarray(y_idx), n_dst)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    empty = np.diff(indptr) == 0
    assert empty.any()
    np.testing.assert_array_equal(np.asarray(got)[empty], 0.0)


def test_hadamard_spmm_smem_window_refills():
    """Both per-edge index arrays share one refilling SMEM window; more
    rows and edges than EDGE_CHUNK make both windows refill."""
    from repro.kernels.hadamard_spmm import hadamard_spmm_pallas
    n_src, n_dst, e = 23, 2 * EDGE_CHUNK + 19, 3 * EDGE_CHUNK + 5
    indptr, x_idx, y_idx, _, feats = _hadamard_case(21, n_src, n_dst, e)
    x, y = feats(n_src, 8), feats(n_dst, 8)
    scale = np.random.default_rng(22).standard_normal(n_dst)
    scale = scale.astype(np.float32)
    args = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(indptr),
            jnp.asarray(x_idx), jnp.asarray(y_idx), n_dst)
    got = hadamard_spmm_pallas(*args, scale=jnp.asarray(scale), slope=0.2)
    want = ref.hadamard_spmm_ref(*args, scale=jnp.asarray(scale), slope=0.2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_hadamard_spmm_integer_exact():
    """Integer-valued embeddings: accumulation order cannot matter, so
    the fused kernel must match the oracle BIT-exactly."""
    from repro.kernels.hadamard_spmm import hadamard_spmm_pallas
    indptr, x_idx, y_idx, _, feats = _hadamard_case(7, 12, 9, 40,
                                                    integer=True)
    x, y = feats(12, 24), feats(9, 24)
    got = hadamard_spmm_pallas(jnp.asarray(x), jnp.asarray(y),
                               jnp.asarray(indptr), jnp.asarray(x_idx),
                               jnp.asarray(y_idx), 9, row_block=4)
    want = ref.hadamard_spmm_ref(jnp.asarray(x), jnp.asarray(y),
                                 jnp.asarray(indptr), jnp.asarray(x_idx),
                                 jnp.asarray(y_idx), 9)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hadamard_spmm_fused_epilogue():
    """Degree-norm scale + leaky-relu applied in-VMEM must match the
    oracle's epilogue composition."""
    from repro.kernels.hadamard_spmm import hadamard_spmm_pallas
    n_src, n_dst, e, d = 10, 8, 25, 36
    indptr, x_idx, y_idx, _, feats = _hadamard_case(11, n_src, n_dst, e)
    x, y = feats(n_src, d), feats(n_dst, d)
    rng = np.random.default_rng(12)
    scale = rng.standard_normal(n_dst).astype(np.float32)
    args = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(indptr),
            jnp.asarray(x_idx), jnp.asarray(y_idx), n_dst)
    got = hadamard_spmm_pallas(*args, scale=jnp.asarray(scale), slope=0.2,
                               row_block=4)
    want = ref.hadamard_spmm_ref(*args, scale=jnp.asarray(scale), slope=0.2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("structure", ["y_is_dst", "x_eq_y"])
def test_hadamard_spmm_structure_variants_match_oracle(structure):
    """The structured XLA routes (no [E, D] intermediate) must equal the
    naive gather/segment oracle when the asserted structure holds."""
    from repro.kernels.hadamard_spmm import hadamard_spmm_xla
    n_src, n_dst, e, d = 9, 7, 28, 20
    indptr, x_idx, y_idx, dst, feats = _hadamard_case(13, n_src, n_dst, e)
    if structure == "y_is_dst":
        y_idx = dst.copy()                      # y rides the destination
        n_y = n_dst
    else:
        y_idx = x_idx.copy()                    # both gathers share an index
        n_y = n_src
    x, y = feats(n_src, d), feats(n_y, d)
    args = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(indptr),
            jnp.asarray(x_idx), jnp.asarray(y_idx), n_dst)
    got = hadamard_spmm_xla(*args, structure=structure)
    want = ref.hadamard_spmm_ref(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_hadamard_spmm_ops_dispatch_parity():
    """kernels.ops dispatch: impl='pallas' and impl='xla' agree."""
    from repro.kernels import ops as kops
    n_src, n_dst, e, d = 8, 6, 20, 12
    indptr, x_idx, y_idx, _, feats = _hadamard_case(17, n_src, n_dst, e)
    x, y = feats(n_src, d), feats(n_dst, d)
    args = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(indptr),
            jnp.asarray(x_idx), jnp.asarray(y_idx), n_dst)
    a = kops.hadamard_spmm(*args, impl="xla")
    b = kops.hadamard_spmm(*args, impl="pallas")
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_hadamard_spmm_bad_structure_raises():
    from repro.kernels.hadamard_spmm import hadamard_spmm_xla
    with pytest.raises(ValueError, match="structure"):
        hadamard_spmm_xla(jnp.zeros((2, 3)), jnp.zeros((2, 3)),
                          jnp.zeros(3, jnp.int32), jnp.zeros(1, jnp.int32),
                          jnp.zeros(1, jnp.int32), 2, structure="nope")


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_hadamard_agg_rematerializing_vjp_finite_difference(impl):
    """The fused Hadamard aggregation's rematerializing VJP (residuals
    are node embeddings only; cotangents are themselves fused calls)
    must match central finite differences in BOTH arguments."""
    rng = np.random.default_rng(3)
    nu, ni, e, d = 7, 6, 16, 4
    user = rng.integers(0, nu, e).astype(np.int32)
    item = rng.integers(0, ni, e).astype(np.int32)
    g = BipartiteCSR(user, item, nu, ni, impl=impl, hadamard="fused")
    xu = jnp.asarray(rng.standard_normal((nu, d)).astype(np.float32))
    xi = jnp.asarray(rng.standard_normal((ni, d)).astype(np.float32))

    def loss_u(xu):
        return jnp.sum(jnp.tanh(g.hadamard_agg_item(xu, xi)))

    def loss_i(xi):
        return jnp.sum(jnp.tanh(g.hadamard_agg_item(xu, xi))) \
            + jnp.sum(g.hadamard_agg_user(xi, xu) ** 2)

    _fd_check(loss_u, xu, [(0, 0), (3, 2), (6, 3)])
    _fd_check(loss_i, xi, [(0, 0), (2, 1), (5, 3)])


def test_hadamard_agg_vjp_matches_autodiff_of_oracle():
    """Fused hadamard_agg gradients equal XLA autodiff of the naive
    gather-multiply-segment composition (which stores [E, D] residuals;
    ours rematerializes them)."""
    rng = np.random.default_rng(4)
    nu, ni, e, d = 9, 8, 26, 5
    user = rng.integers(0, nu, e).astype(np.int32)
    item = rng.integers(0, ni, e).astype(np.int32)
    g = BipartiteCSR(user, item, nu, ni, impl="xla", hadamard="fused")
    xu = rng.standard_normal((nu, d)).astype(np.float32)
    xi = rng.standard_normal((ni, d)).astype(np.float32)

    def fused(xu, xi):
        return jnp.sum(jnp.sin(g.hadamard_agg_item(xu, xi)))

    def naive(xu, xi):
        msgs = xu[g.ui_src] * xi[g.ui_dst]
        agg = jax.ops.segment_sum(msgs, g.ui_dst, num_segments=ni)
        return jnp.sum(jnp.sin(agg))

    gu_f, gi_f = jax.grad(fused, argnums=(0, 1))(jnp.asarray(xu),
                                                 jnp.asarray(xi))
    gu_n, gi_n = jax.grad(naive, argnums=(0, 1))(jnp.asarray(xu),
                                                 jnp.asarray(xi))
    np.testing.assert_allclose(gu_f, gu_n, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gi_f, gi_n, rtol=1e-4, atol=1e-5)


# ------------------------------------------------- fused serving kernel
def _fused_both(ue, ie, seen, mask, k, blk):
    """(xla-ref, pallas-interpret) results of the fused serving kernel."""
    from repro.kernels import ops as kops
    ni = ie.shape[0]
    a = kops.fused_topk_score(jnp.asarray(ue), jnp.asarray(ie),
                              jnp.asarray(seen), jnp.asarray(mask),
                              k=k, n_items=ni, item_block=blk, impl="xla")
    b = kops.fused_topk_score(jnp.asarray(ue), jnp.asarray(ie),
                              jnp.asarray(seen), jnp.asarray(mask),
                              k=k, n_items=ni, item_block=blk, impl="pallas")
    return a, b


def _streamed_reference(ue, ie, seen, mask, k, blk):
    """The pre-fused streamed sweep as oracle: block-major _merge_block
    calls over the same block schedule (bit-exact tie contract)."""
    from repro.eval import topk as streaming
    b_users = ue.shape[0]
    ni = ie.shape[0]
    carry_s = jnp.full((b_users, k), -np.inf, jnp.float32)
    carry_i = jnp.full((b_users, k), -1, jnp.int32)
    for b0 in range(0, -(-ni // blk) * blk, blk):
        ids_np = np.arange(b0, b0 + blk)
        valid = ids_np < ni
        block_ids = jnp.asarray(np.where(valid, ids_np, -1).astype(np.int32))
        ie_blk = jnp.asarray(ie[np.where(valid, ids_np, 0)])
        carry_s, carry_i = streaming._merge_block(
            jnp.asarray(ue), ie_blk, block_ids, jnp.asarray(seen),
            jnp.asarray(mask), jnp.int32(b0), carry_s, carry_i, k=k)
    return np.asarray(carry_s), np.asarray(carry_i)


@pytest.mark.parametrize("case", [
    "integer_ties",      # many exactly-equal scores -> id-asc order
    "neg_zero",          # -0.0 scores must canonicalize to +0.0
    "k_gt_catalogue",    # K > I: tail slots are (-inf, -1)
    "fully_masked",      # a user with every item seen
    "ragged_d",          # D % 128 != 0, B % tile != 0, I % blk != 0
    "empty_seen",        # zero-width seen CSR
])
def test_fused_kernel_adversarial_parity(case):
    rng = np.random.default_rng(abs(hash(case)) % 2**31)
    b, ni, d, k, blk, L = 9, 37, 12, 5, 8, 4
    ue = rng.integers(-2, 3, (b, d)).astype(np.float32)
    ie = rng.integers(-2, 3, (ni, d)).astype(np.float32)
    seen = rng.integers(0, ni, (b, L)).astype(np.int32)
    mask = rng.random((b, L)) < 0.5
    if case == "integer_ties":
        ie = np.repeat(ie[: ni // 3 + 1], 3, axis=0)[:ni]  # duplicate rows
    elif case == "neg_zero":
        ue = np.full((b, d), -1.0, np.float32)
        ie[::2] = 0.0                       # (-1)·0 = -0.0 pre-canonical
    elif case == "k_gt_catalogue":
        ni, k = 6, 11
        ie = ie[:ni]
        seen = np.minimum(seen, ni - 1)
    elif case == "fully_masked":
        ni, L = 6, 6
        ie = ie[:ni]
        seen = np.broadcast_to(np.arange(ni, dtype=np.int32), (b, ni)).copy()
        mask = np.ones((b, ni), bool)       # every candidate masked
    elif case == "ragged_d":
        d, b, blk = 130, 7, 5               # nothing divides anything
        ue = rng.integers(-2, 3, (b, d)).astype(np.float32)
        ie = rng.integers(-2, 3, (ni, d)).astype(np.float32)
        seen = seen[:b]
        mask = mask[:b]
    elif case == "empty_seen":
        seen = np.zeros((b, 0), np.int32)
        mask = np.zeros((b, 0), bool)
    (s_x, i_x), (s_p, i_p) = _fused_both(ue, ie, seen, mask, k, blk)
    s_ref, i_ref = _streamed_reference(ue, ie, seen, mask, k, blk)
    np.testing.assert_array_equal(np.asarray(s_x), s_ref)
    np.testing.assert_array_equal(np.asarray(i_x), i_ref)
    np.testing.assert_array_equal(np.asarray(s_p), s_ref)
    np.testing.assert_array_equal(np.asarray(i_p), i_ref)
    if case == "fully_masked":
        assert (np.asarray(i_x) == -1).all()
        assert np.isneginf(np.asarray(s_x)).all()
    if case == "k_gt_catalogue":
        assert (np.asarray(i_x)[:, ni:] == -1).all()
        assert np.isneginf(np.asarray(s_x)[:, ni:]).all()


@pytest.mark.parametrize("embed_store", ["fp32", "int8"])
def test_cache_on_off_bit_identity_sweep(embed_store):
    """Randomized serving sweeps: cache-enabled recommendations are
    bit-identical to cache-off for every placement/store combination."""
    from repro.eval.recommender import Recommender
    for seed in range(6):
        rng = np.random.default_rng(seed)
        nu, ni, d = int(rng.integers(5, 40)), int(rng.integers(5, 50)), 8
        ue = rng.integers(-3, 4, (nu, d)).astype(np.float32)
        ie = rng.integers(-3, 4, (ni, d)).astype(np.float32)
        ne = int(rng.integers(0, nu * 3))
        user = np.sort(rng.integers(0, nu, ne))
        item = rng.integers(0, ni, ne)
        indptr = np.searchsorted(user, np.arange(nu + 1)).astype(np.int64)
        kw = dict(seen_indptr=indptr, seen_items=item.astype(np.int64),
                  k=int(rng.integers(1, 9)), user_batch=4,
                  topology="uniform", embed_store=embed_store,
                  pins={"serve/user_embed": "slow",
                        "serve/item_embed": "slow"})
        plain = Recommender(ue, ie, **kw)
        cached = Recommender(ue, ie, cache_rows=int(rng.integers(1, 16)),
                             **kw)
        for _ in range(3):
            q = rng.integers(0, nu, int(rng.integers(1, 20)))
            i0, s0 = plain.recommend(q)
            i1, s1 = cached.recommend(q)
            np.testing.assert_array_equal(i0, i1)
            np.testing.assert_array_equal(s0, s1)


# --------------------------------------------------- ann coarse kernel
@pytest.mark.parametrize("b,nb,d", [
    (9, 37, 12),       # nothing tile-aligned
    (1, 1, 130),       # single user, single block, D over one lane tile
    (7, 129, 8),       # n_blocks just over the 128-lane tile
])
def test_ann_block_scores_pallas_matches_xla(b, nb, d):
    """The ANN coarse stage (int8 centroid dot + norm·radius bound) on
    adversarial shapes: pallas interpret vs the kernels/ref.py oracle,
    through the ops dispatch both ways."""
    from repro.kernels import ops as kops
    rng = np.random.default_rng(hash((b, nb, d)) % 2**31)
    ue = rng.standard_normal((b, d)).astype(np.float32)
    cq = rng.integers(-127, 128, (nb, d)).astype(np.int8)
    scale = rng.uniform(1e-3, 0.1, nb).astype(np.float32)
    radius = rng.uniform(0.0, 2.0, nb).astype(np.float32)
    args = (jnp.asarray(ue), jnp.asarray(cq), jnp.asarray(scale),
            jnp.asarray(radius))
    want = ref.ann_block_scores_ref(*args)
    got = kops.ann_block_scores(*args, impl="pallas")
    assert got.shape == (b, nb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(kops.ann_block_scores(*args, impl="xla")),
        np.asarray(want))
    # radius=0 degenerates to the pure centroid affinity (what the
    # serving index ranks blocks by)
    aff = kops.ann_block_scores(args[0], args[1], args[2],
                                jnp.zeros(nb, jnp.float32), impl="pallas")
    np.testing.assert_allclose(
        np.asarray(aff), np.asarray(ue @ (cq.astype(np.float32)
                                          * scale[:, None]).T),
        rtol=1e-5, atol=1e-5)
