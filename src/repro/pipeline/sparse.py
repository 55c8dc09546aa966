"""CSR-routed sparse aggregation for the training pipeline.

The seed models aggregated through jnp segment ops directly; the
pipeline instead pre-sorts the bipartite graph into the two CSR
directions once (host side) and routes every aggregation through
``repro.kernels.ops.spmm_csr`` — the Pallas TPU kernel on TPU backends,
the XLA reference oracle elsewhere (``default_impl``).

Autodiff: ``pallas_call`` has no registered VJP, so each aggregation op
carries a custom VJP that expresses its gradient as the *reverse
direction's* SpMM — the paper's observation (§4) that GNN gradients map
onto the same SDDMM/SpMM kernels, made explicit:

  * adjacency matmul (gather=True SpMM):  d/dx (A x) = A^T ct — the
    opposite-direction gather-SpMM;
  * edge aggregation (gather=False SpMM): d/dvalues = ct[dst_e] — an
    SDDMM-copy gather.

LightGCN's symmetric normalization 1/sqrt(d_u d_i) is separable, so the
kernels run unweighted and the degree scalings apply at node level —
no [E, D] message matrix is ever materialized for LightGCN/GCN, nor
for NGCF, whose Hadamard term factors into node rows times the same
propagation (only its 'composed' route forms one edge matrix per
layer, and the planner's tensor set reflects that).

Sharded dispatch: alongside ``pallas``/``xla`` there is a ``ring``
route (``ShardPlan.wants_ring``) that runs node aggregation through
``dist.ring_spmm`` over the *unified* node space (users then items,
padded to a multiple of the shard count): features row-sharded over the
device ring, edges bucketed by (dst device, ring distance) into CSR
buckets, each ring step aggregating its bucket with the same
gather-SpMM kernel as the CSR path (``impl``) and then permuting the
block on — the paper's NUMA-blocked Fig 11 schedule as a device ring;
the permute follows the bucket's compute, so little of it is hidden.
The symmetric
propagation becomes ONE ring SpMM per layer (both directions at once,
since the unified adjacency is symmetric), and every ring op carries a
custom VJP that is the transpose-direction ring — the same
gradients-map-onto-the-same-kernels structure (§4) as the CSR path.

``BipartiteCSR`` is a pytree: its index arrays (and the ring bucket
cubes) are the leaves, so a jitted step takes the graph as an argument
instead of baking millions of indices into the program as constants.
"""
from __future__ import annotations

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ops as kops
from repro.kernels.spmm import build_csr_by_dst
from repro.pipeline.shard import ShardPlan


def default_impl() -> str:
    """Kernel dispatch per backend: Pallas on TPU, XLA oracle elsewhere
    (interpret-mode Pallas is correct but far too slow for training)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _adj_matmul(impl, n_dst, n_src, x, fwd, bwd):
    """out = A x via gather-SpMM; VJP = A^T ct via the reverse CSR.
    ``fwd``/``bwd``: (indptr, src) of the forward / reverse CSR."""
    return kops.spmm_csr("sum", x, *fwd, n_dst, gather=True, impl=impl)


def _adj_matmul_fwd(impl, n_dst, n_src, x, fwd, bwd):
    return _adj_matmul(impl, n_dst, n_src, x, fwd, bwd), bwd


def _adj_matmul_bwd(impl, n_dst, n_src, bwd, ct):
    return (kops.spmm_csr("sum", ct, *bwd, n_src, gather=True, impl=impl),
            None, None)


_adj_matmul.defvjp(_adj_matmul_fwd, _adj_matmul_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _edge_agg(impl, n_dst, values, indptr, dst_sorted):
    """out[v] = sum of edge values into v (values already dst-sorted);
    VJP = ct[dst_e], the SDDMM-copy gather."""
    # src_sorted operand unused when gather=False; pass dst_sorted
    return kops.spmm_csr("sum", values, indptr, dst_sorted, n_dst,
                         gather=False, impl=impl)


def _edge_agg_fwd(impl, n_dst, values, indptr, dst_sorted):
    return _edge_agg(impl, n_dst, values, indptr, dst_sorted), dst_sorted


def _edge_agg_bwd(impl, n_dst, dst_sorted, ct):
    return ct[dst_sorted], None, None


_edge_agg.defvjp(_edge_agg_fwd, _edge_agg_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _hadamard_agg(impl, n_dst, n_src, x, y, fwd, bwd):
    """Fused Hadamard aggregation with a REMATERIALIZING custom VJP.

    Forward: out[v] = sum_{e: dst_e = v} x[src_e] * y[v] — one
    ``ops.hadamard_spmm`` call (structure ``y_is_dst``: the second
    factor rides the destination), no [E, D] message matrix.
    ``fwd`` = (indptr, src, dst) of the forward CSR, ``bwd`` =
    (indptr, src) of the transpose CSR.

    Backward saves only the NODE embeddings (x, y) as residuals and
    recomputes the edge products inside the cotangent kernels instead
    of storing [E, D] residuals; both cotangent paths are themselves
    fused gather-multiply-aggregate calls over the same CSR pair:

      d_x[s] = sum_{e: src_e = s} ct[dst_e] * y[dst_e]
               — the transpose CSR with BOTH gathers through its source
                 index (structure ``x_eq_y``: the product forms at node
                 level, gathered once);
      d_y[v] = ct[v] * sum_{e: dst_e = v} x[src_e]
               — the forward CSR with ct riding the destination
                 (structure ``y_is_dst`` again).
    """
    return kops.hadamard_spmm(x, y, *fwd, n_dst, structure="y_is_dst",
                              impl=impl)


def _hadamard_agg_fwd(impl, n_dst, n_src, x, y, fwd, bwd):
    return _hadamard_agg(impl, n_dst, n_src, x, y, fwd, bwd), \
        (x, y, fwd, bwd)


def _hadamard_agg_bwd(impl, n_dst, n_src, res, ct):
    x, y, fwd, (indptr_b, src_b) = res
    d_x = kops.hadamard_spmm(ct, y, indptr_b, src_b, src_b, n_src,
                             structure="x_eq_y", impl=impl)
    d_y = kops.hadamard_spmm(x, ct, *fwd, n_dst, structure="y_is_dst",
                             impl=impl)
    return d_x, d_y, None, None


_hadamard_agg.defvjp(_hadamard_agg_fwd, _hadamard_agg_bwd)


def _ring_matmul(fwd_fn, bwd_fn):
    """x_pad -> A x_pad through one ring closure, with the VJP riding the
    transpose ring.  The bucket arrays are arguments (no cotangent)."""

    @jax.custom_vjp
    def matmul(x, fwd, bwd):
        return fwd_fn(x, *fwd)

    def fwd_rule(x, fwd, bwd):
        return fwd_fn(x, *fwd), bwd

    def bwd_rule(bwd, ct):
        return bwd_fn(ct, *bwd), None, None

    matmul.defvjp(fwd_rule, bwd_rule)
    return matmul


# ---------------------------------------------------------------- ring
class _RingGraph:
    """Ring-SpMM aggregations over the unified node space of one
    bipartite graph (user u -> row u, item i -> row n_users + i, rows
    padded to a multiple of the shard count).  Padded rows own no
    edges, so they aggregate to zero and are sliced back off.

    ``sym`` applies the symmetric adjacency (both edge directions in
    one ring pass); ``ui``/``iu`` apply only the user->item /
    item->user direction; ``*_T`` are their exact transposes.  Each
    ring step aggregates through the graph's kernel dispatch ``impl``.
    Each op's bucket cubes (``arrays``: source rows and row pointers,
    device arrays row-sharded over the
    mesh) are built on first use, or up front by ``build`` — the
    pipeline builds the ops its model uses (``ModelSpec.ring_ops``)
    before the first trace, so the cubes reach the jitted step as
    arguments.  A view unflattened over new leaves (``frozen``: the
    graph argument inside a jitted step) cannot build: an op missing
    there raises instead of baking host-bucketed cubes into the program.
    """

    def __init__(self, shard: ShardPlan, user: np.ndarray, item: np.ndarray,
                 n_users: int, n_items: int, impl: str):
        from repro.dist.ring_spmm import bucket_edges, make_ring_spmm
        self._bucket_edges = bucket_edges
        self._make_ring_spmm = make_ring_spmm
        self.shard = shard
        self.impl = impl
        self.n_users = int(n_users)
        self.n_items = int(n_items)
        self.part = shard.partition(n_users + n_items)
        self._src_ui = np.asarray(user, np.int64)
        self._dst_ui = np.asarray(item, np.int64) + n_users
        self.arrays: dict[str, tuple] = {}
        self._fns: dict = {}
        self.frozen = False

    def _banded(self) -> bool:
        s = self.shard.ring_steps
        return s is not None and s < self.shard.n_shards

    def _canon(self, which: str) -> str:
        """Unbanded, transposes alias the plain reverses (sym is
        self-adjoint, ui/iu are mutual transposes)."""
        if self._banded():
            return which
        return {"sym_T": "sym", "ui_T": "iu", "iu_T": "ui"}.get(which, which)

    def _steps(self, key: str) -> int | None:
        """Banded transposes run over the full ring (see ``_edges``)."""
        return None if key.endswith("_T") else self.shard.ring_steps

    def _band_kept(self, src: np.ndarray, dst: np.ndarray):
        """The subset of edges the banded forward actually applies."""
        p = self.shard.n_shards
        n_local = self.part.n_local
        rel = (src // n_local - dst // n_local) % p
        keep = rel < self.shard.ring_steps
        return src[keep], dst[keep]

    def _edges(self, key: str):
        """(src, dst) of one canonical op.  Banded ``*_T`` keys are the
        exact transposes of the banded forwards: the band keeps edge
        (s, d) by the ring distance of s's owner AHEAD of d's — an
        asymmetric criterion — so the VJP cannot reuse a banded reverse
        ring (it would apply a different edge set than A^T).  Instead
        the transpose buckets the reversed KEPT edges over the full
        ring."""
        s, d = self._src_ui, self._dst_ui
        sym_s, sym_d = np.concatenate([s, d]), np.concatenate([d, s])
        base = {"sym": (sym_s, sym_d), "ui": (s, d), "iu": (d, s)}
        if key in base:
            return base[key]
        ks, kd = self._band_kept(*base[key[:-2]])
        return kd, ks

    def build(self, *keys: str) -> None:
        """Bucket the given ops' edges and place the cubes on the mesh,
        each device holding its dst rows.  Concrete even when first
        reached inside a trace, so nothing traced is memoized."""
        for key in map(self._canon, keys):
            if key in self.arrays:
                continue
            if self.frozen:
                raise RuntimeError(
                    f"ring op {key!r} has no bucket cubes in this graph "
                    f"view; build it before tracing (BipartiteCSR."
                    f"build_ring, ModelSpec.ring_ops)")
            src_l, indptr, _ = self._bucket_edges(
                *self._edges(key), self.part.n_pad, self.shard.n_shards,
                n_steps=self._steps(key))
            sh = jax.sharding.NamedSharding(
                self.shard.build_mesh(),
                jax.sharding.PartitionSpec(self.shard.dp, None, None))
            with jax.ensure_compile_time_eval():
                self.arrays[key] = tuple(jax.device_put(a, sh)
                                         for a in (src_l, indptr))

    def _ring_fn(self, n_steps):
        if n_steps not in self._fns:
            self._fns[n_steps] = self._make_ring_spmm(
                self.shard.build_mesh(), self.shard.dp, self.part.n_local,
                n_steps=n_steps, quantize=self.shard.ring_quant,
                impl=self.impl)
        return self._fns[n_steps]

    def matmul(self, which: str, x):
        """x_pad [n_pad, D] -> A_which x_pad, VJP = the transpose ring."""
        fwd, bwd = self._canon(which), self._canon(which + "_T")
        self.build(fwd, bwd)
        key = ("vjp", self._steps(fwd), self._steps(bwd))
        if key not in self._fns:
            self._fns[key] = _ring_matmul(self._ring_fn(key[1]),
                                          self._ring_fn(key[2]))
        return self._fns[key](x, self.arrays[fwd], self.arrays[bwd])

    def nbytes(self) -> int:
        """Bytes of the built bucket cubes (the pipeline builds its
        model's ops before planning, so the planner sees them all)."""
        return sum(int(a.nbytes) for arrs in self.arrays.values()
                   for a in arrs)

    # ------------------------------------------------------- lifted ops
    def _lift(self, x, offset: int):
        """[n, D] rows -> unified padded [n_pad, D] at row ``offset``."""
        z = jnp.zeros((self.part.n_pad, x.shape[-1]), x.dtype)
        return jax.lax.dynamic_update_slice(z, x, (offset, 0))

    def _apply(self, which, x, in_off, out_off, n_out):
        h = self.matmul(which, self._lift(x, in_off))
        return jax.lax.dynamic_slice(h, (out_off, 0), (n_out, x.shape[-1]))

    def u2i(self, x):
        """x_user [n_users, D] -> [n_items, D]; the VJP rides the
        transpose ring (item->user direction)."""
        return self._apply("ui", x, 0, self.n_users, self.n_items)

    def i2u(self, x):
        return self._apply("iu", x, self.n_users, 0, self.n_users)


_CSR_FIELDS = ("ui_indptr", "ui_src", "ui_dst", "iu_indptr", "iu_src",
               "iu_dst", "perm_ui_to_iu", "rsqrt_du", "rsqrt_di")


class BipartiteCSR:
    """Both CSR directions of a user-item graph + kernel-routed ops.

    Built once per training run (host-side sort).  The graph is a
    pytree whose leaves are its index arrays and ring cubes, so jitted
    steps take it as an argument: the program stays small and its cache
    key carries shapes, not the graph.

      agg_u2i(x_user)  -> [n_items, D]   unweighted A^T x
      agg_i2u(x_item)  -> [n_users, D]   unweighted A x
      edge_agg_item(m) -> [n_items, D]   m in ui (item-sorted) edge order
      edge_agg_user(m) -> [n_users, D]   m in iu (user-sorted) edge order
      perm_ui_to_iu    reorders ui-order edge values into iu order (the
                       O3 SDDMM-reuse path: one Hadamard per layer)
      hadamard_agg_item(xu, xi) -> [n_items, D]   fused sum_e xu[u_e]*xi[i]
      hadamard_agg_user(xi, xu) -> [n_users, D]   fused sum_e xi[i_e]*xu[u]
                       (rematerializing VJP, no [E, D] message matrix)

    ``hadamard`` selects how NGCF forms its Hadamard term
    ``sum_e p_ui x_src * x_dst`` (``p_ui = 1/sqrt(d_u d_i)``), which
    factors as ``x_dst * (L x)_dst``: 'auto' takes that factored form
    (the layer's own ``sym_propagate`` times the node rows, no further
    aggregation, under every dispatch); 'fused' aggregates it again
    through the ops above on degree-scaled rows (no [E, D] matrix);
    'composed' forms the [E, D] products and edge-aggregates them.  The
    ring dispatch has no fused gather-multiply-aggregate, so 'fused'
    falls back to 'composed' there.  ``hadamard_route`` is the resolved
    choice ('factored', 'fused' or 'composed'), read by the registry
    forward and the planner.
    """

    def __init__(self, user: np.ndarray, item: np.ndarray, n_users: int,
                 n_items: int, edge_mask: np.ndarray | None = None,
                 impl: str | None = None, shard: ShardPlan | None = None,
                 hadamard: str = "auto"):
        # 'ring' is a first-class dispatch value: it forces the sharded
        # aggregation route (degenerate 1-device ring when no mesh is
        # given); node-level kernels still need a pallas/xla backend.
        if impl == "ring" and shard is None:
            shard = ShardPlan(spmm="ring")
        self.impl = default_impl() if impl in (None, "ring") else impl
        self.shard = shard
        self.spmm = "ring" if (shard is not None and shard.wants_ring) \
            else self.impl
        user = np.asarray(user, np.int32)
        item = np.asarray(item, np.int32)
        if edge_mask is not None:
            keep = np.asarray(edge_mask).astype(bool)
            user, item = user[keep], item[keep]
        self.n_users = int(n_users)
        self.n_items = int(n_items)
        self.n_edges = len(user)

        ui_indptr, ui_src, perm_ui = build_csr_by_dst(item, user, n_items)
        iu_indptr, iu_src, perm_iu = build_csr_by_dst(user, item, n_users)
        # host copies of the user-CSR: the eval/serving seen-item mask is
        # built from these (O(E) structure, never a dense U×I mask)
        self._seen_indptr = np.asarray(iu_indptr, np.int64)
        self._seen_items = np.asarray(iu_src, np.int64)
        inv_ui = np.empty(self.n_edges, np.int64)
        inv_ui[perm_ui] = np.arange(self.n_edges)
        self.perm_ui_to_iu = jnp.asarray(inv_ui[perm_iu].astype(np.int32))

        self.ui_indptr = jnp.asarray(ui_indptr)
        self.ui_src = jnp.asarray(ui_src)                  # user per edge
        self.ui_dst = jnp.asarray(item[perm_ui])           # item per edge
        self.iu_indptr = jnp.asarray(iu_indptr)
        self.iu_src = jnp.asarray(iu_src)                  # item per edge
        self.iu_dst = jnp.asarray(user[perm_iu])           # user per edge

        du = np.bincount(user, minlength=n_users).astype(np.float32)
        di = np.bincount(item, minlength=n_items).astype(np.float32)
        self.rsqrt_du = jnp.asarray(1.0 / np.sqrt(np.maximum(du, 1.0)))
        self.rsqrt_di = jnp.asarray(1.0 / np.sqrt(np.maximum(di, 1.0)))
        if shard is not None and shard.is_sharded:
            # replicated once on the mesh (the planner prices the CSR at
            # full size per device), not re-sent with every step
            rep = jax.sharding.NamedSharding(shard.build_mesh(),
                                             jax.sharding.PartitionSpec())
            for f in _CSR_FIELDS:
                setattr(self, f, jax.device_put(getattr(self, f), rep))

        self._ring = None
        if self.spmm == "ring":
            self._ring = _RingGraph(self.shard, user, item, n_users, n_items,
                                    self.impl)
        if hadamard not in ("auto", "fused", "composed"):
            raise ValueError(f"hadamard must be 'auto', 'fused' or "
                             f"'composed', got {hadamard!r}")
        self.hadamard_route = (
            "factored" if hadamard == "auto" else
            "fused" if hadamard == "fused" and self.spmm != "ring"
            else "composed")

    # ------------------------------------------------------------ pytree
    def _tree_flatten(self):
        ring = self._ring.arrays if self._ring is not None else {}
        keys = tuple(sorted(ring))
        leaves = (tuple(getattr(self, f) for f in _CSR_FIELDS),
                  tuple(ring[k] for k in keys))
        return leaves, (self, keys)

    @staticmethod
    def _tree_unflatten(aux, leaves):
        """A view of the same graph over new leaves (tracers inside a
        jitted step); host-side structure is shared, not rebuilt."""
        skeleton, keys = aux
        g = copy.copy(skeleton)
        for f, a in zip(_CSR_FIELDS, leaves[0]):
            setattr(g, f, a)
        if skeleton._ring is not None:
            g._ring = copy.copy(skeleton._ring)
            g._ring.arrays = dict(zip(keys, leaves[1]))
            g._ring.frozen = True
        return g

    def build_ring(self, ops) -> None:
        """Build the ring cubes of the given ops (e.g. ``("sym",
        "sym_T")``) now, so they ride into jitted steps as arguments;
        a no-op off the ring dispatch."""
        if self._ring is not None:
            self._ring.build(*ops)

    # ------------------------------------------------------------ ops
    def agg_u2i(self, x):
        with obs.agg_scope("u2i"):
            if self._ring is not None:
                return self._ring.u2i(x)
            return _adj_matmul(self.impl, self.n_items, self.n_users, x,
                               (self.ui_indptr, self.ui_src),
                               (self.iu_indptr, self.iu_src))

    def agg_i2u(self, x):
        with obs.agg_scope("i2u"):
            if self._ring is not None:
                return self._ring.i2u(x)
            return _adj_matmul(self.impl, self.n_users, self.n_items, x,
                               (self.iu_indptr, self.iu_src),
                               (self.ui_indptr, self.ui_src))

    # edge-level aggregation ([E, D] values, dst-sorted) stays on the
    # node-local kernel path under every dispatch: the values are
    # already per-edge, so there is no feature block to rotate
    def edge_agg_item(self, values):
        with obs.agg_scope("edge"):
            return _edge_agg(self.impl, self.n_items, values,
                             self.ui_indptr, self.ui_dst)

    def edge_agg_user(self, values):
        with obs.agg_scope("edge"):
            return _edge_agg(self.impl, self.n_users, values,
                             self.iu_indptr, self.iu_dst)

    def hadamard_agg_item(self, xu, xi):
        with obs.agg_scope("hadamard"):
            return _hadamard_agg(
                self.impl, self.n_items, self.n_users, xu, xi,
                (self.ui_indptr, self.ui_src, self.ui_dst),
                (self.iu_indptr, self.iu_src))

    def hadamard_agg_user(self, xi, xu):
        with obs.agg_scope("hadamard"):
            return _hadamard_agg(
                self.impl, self.n_users, self.n_items, xi, xu,
                (self.iu_indptr, self.iu_src, self.iu_dst),
                (self.ui_indptr, self.ui_src))

    def seen_csr(self) -> tuple[np.ndarray, np.ndarray]:
        """(indptr, items) numpy user-CSR over the train interactions —
        the exclusion structure for streaming eval and serving
        (``repro.eval``): items[indptr[u]:indptr[u+1]] are user u's
        already-seen item ids."""
        return self._seen_indptr, self._seen_items

    def csr_nbytes(self) -> int:
        """Bytes of the CSR adjacency alone (both directions) — stays
        fully REPLICATED per device under every dispatch (edge aggs and
        the eval seen-structure still read it)."""
        arrs = (self.ui_indptr, self.ui_src, self.ui_dst, self.iu_indptr,
                self.iu_src, self.iu_dst, self.perm_ui_to_iu)
        return int(sum(a.size * a.dtype.itemsize for a in arrs))

    def ring_nbytes(self) -> int:
        """Bytes of the built ring bucket cubes; 0 off the ring
        dispatch.  The cubes are dst-sharded over the mesh — each
        device holds 1/P of them."""
        return self._ring.nbytes() if self._ring is not None else 0

    def graph_nbytes(self) -> int:
        """Bytes of the whole adjacency structure (CSR + ring cubes)."""
        return self.csr_nbytes() + self.ring_nbytes()

    def sym_propagate(self, x_user, x_item):
        """One symmetric-normalized propagation (LightGCN/GCN layer):
        h_i = sum_e x_u / sqrt(d_u d_i), both directions.  The separable
        coefficient lets both directions run as unweighted gather-SpMM —
        and, under the ring dispatch, as ONE ring SpMM over the unified
        (symmetric) adjacency: both directions ride a single rotation
        schedule, the distributed analogue of the paper's fused
        NUMA-blocked pass."""
        if self._ring is not None:
            # the whole pass: the lift into the unified node space and
            # the slices back reshard over the mesh (collective-permutes)
            with obs.agg_scope("sym"):
                part = self._ring.part
                z = jnp.concatenate([x_user * self.rsqrt_du[:, None],
                                     x_item * self.rsqrt_di[:, None]], axis=0)
                h = part.trim(self._ring.matmul("sym", part.pad_rows(z)))
                return (h[:self.n_users] * self.rsqrt_du[:, None],
                        h[self.n_users:] * self.rsqrt_di[:, None])
        h_item = self.agg_u2i(x_user * self.rsqrt_du[:, None]) \
            * self.rsqrt_di[:, None]
        h_user = self.agg_i2u(x_item * self.rsqrt_di[:, None]) \
            * self.rsqrt_du[:, None]
        return h_user, h_item


jax.tree_util.register_pytree_node(BipartiteCSR, BipartiteCSR._tree_flatten,
                                   BipartiteCSR._tree_unflatten)
