"""Main-path kernels compiled for a described TPU v5e (no chip needed).

Interpret mode accepts layouts the TPU compiler refuses (SMEM over its
1 MiB, unaligned slices, primitives Mosaic cannot lower), so these
tests compile each kernel of the training and serving path with the
installed TPU compiler at main-path widths: the m-x25 node counts,
D=128, and an edge count past the old whole-array SMEM prefetch limit.

The topology is described inside a module fixture — never at import —
so every pytest worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.kernels.ann import ann_block_scores_pallas
from repro.kernels.hadamard_spmm import hadamard_spmm_pallas
from repro.kernels.spmm import ring_plan, spmm_csr_pallas
from repro.kernels.topk_score import fused_topk_score_pallas

N_USERS, N_ITEMS, D = 349_184, 53_248, 128
E = 1 << 20            # 4 MiB of int32 per index array: 4x the SMEM
E_CELL = 4_194_228     # lightgcn-m25-train's graph: 2^22 edges, deduplicated


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("gather", [True, False])
def test_spmm_compiles_past_smem_limit(one_chip, gather):
    vals = (N_ITEMS, D) if gather else (E, D)
    _compile(functools.partial(spmm_csr_pallas, "sum", n_nodes=N_USERS,
                               gather=gather, interpret=False),
             one_chip, (vals, jnp.float32), ((N_USERS + 1,), jnp.int32),
             ((E,), jnp.int32))


@pytest.mark.parametrize("n_dst,n_src", [(N_ITEMS, N_USERS),
                                         (N_USERS, N_ITEMS)])
def test_spmm_ring_compiles_at_the_training_cells_shapes(one_chip, n_dst,
                                                         n_src):
    """The two call shapes of the one-chip LightGCN step: item rows
    gathering from the user table, and user rows from the item table,
    each traced once at the ring depth the shapes pick."""
    depth = ring_plan(n_dst, D)[1]
    before = obs.counters()["spmm_inflight"].get(depth, 0)
    _compile(functools.partial(spmm_csr_pallas, "sum", n_nodes=n_dst,
                               gather=True, interpret=False),
             one_chip, ((n_src, D), jnp.float32), ((n_dst + 1,), jnp.int32),
             ((E_CELL,), jnp.int32))
    assert obs.counters()["spmm_inflight"][depth] == before + 1


def test_ring_spmm_compiles_pallas_under_shard_map(topo, monkeypatch):
    """The ring cell's aggregation on the four chips of the described
    host: each bucket through the Pallas SpMM inside the ring's
    shard_map, the blocks rotating by collective-permute.  Shapes: the
    cell's padded node count over four chips and a bucket about as long
    as its largest."""
    from repro.dist.ring_spmm import make_ring_spmm
    from repro.kernels import ops as kops
    mesh = Mesh(np.array(topo.devices), ("data",))
    n_pad, bucket = 402_432, 2_200_000
    n_local = n_pad // 4
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    ring = make_ring_spmm(mesh, "data", n_local, impl="pallas")
    rows = NamedSharding(mesh, P("data", None))
    cube = NamedSharding(mesh, P("data", None, None))
    before = obs.counters()["ring_route"].get("pallas", 0)
    text = jax.jit(ring).lower(
        jax.ShapeDtypeStruct((n_pad, D), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((4, 4, bucket), jnp.int32, sharding=cube),
        jax.ShapeDtypeStruct((4, 4, n_local + 1), jnp.int32, sharding=cube),
    ).compile().as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text
    assert obs.counters()["ring_route"]["pallas"] == before + 1


def test_hadamard_spmm_compiles_past_smem_limit(one_chip):
    def fn(x, y, indptr, x_idx, y_idx, scale):
        return hadamard_spmm_pallas(x, y, indptr, x_idx, y_idx, N_USERS,
                                    scale=scale, slope=0.2, interpret=False)

    _compile(fn, one_chip, ((N_ITEMS, D), jnp.float32),
             ((N_USERS, D), jnp.float32), ((N_USERS + 1,), jnp.int32),
             ((E,), jnp.int32), ((E,), jnp.int32), ((N_USERS,), jnp.float32))


def test_fused_topk_score_compiles(one_chip):
    # 300 users (not a whole number of 128-lane tiles) whose heaviest
    # seen history is far longer than one VMEM seen slab
    b, seen_len = 300, 40_000

    def fn(ue, table, seen, mask):
        return fused_topk_score_pallas(ue, table, seen, mask, k=20,
                                       item_block=1024, n_items=N_ITEMS,
                                       interpret=False)

    _compile(fn, one_chip, ((b, D), jnp.float32),
             ((N_ITEMS, D), jnp.float32), ((b, seen_len), jnp.int32),
             ((b, seen_len), jnp.bool_))


def test_ann_block_scores_compiles(one_chip):
    nb = N_ITEMS // 32

    def fn(ue, cq, scale, radius):
        return ann_block_scores_pallas(ue, cq, scale, radius,
                                       interpret=False)

    _compile(fn, one_chip, ((256, D), jnp.float32), ((nb, D), jnp.int8),
             ((nb,), jnp.float32), ((nb,), jnp.float32))


@pytest.mark.parametrize("arch", ["lightgcn", "ngcf"])
def test_step_kernels_run_under_the_aggregation_scope(one_chip, arch,
                                                      monkeypatch):
    """Every Pallas call of the training step (the SpMMs, and NGCF's
    fused Hadamard-SpMMs), forward and backward, carries the ``agg``
    scope in its op metadata: the name stack the device trace gives the
    op as its ``tf_op``, which ``agg.ms`` reads."""
    import re

    from repro.data import synth
    from repro.kernels import ops as kops
    from repro.pipeline.engine import PipelineConfig, build_pipeline

    data = synth.generate_bipartite(40, 30, 300, seed=0)
    pipe = build_pipeline(PipelineConfig(arch=arch, embed_dim=D,
                                         microbatch=16, target_batch=16,
                                         impl="pallas"), data)
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)

    def described(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(described, pipe.init_state()["params"])
    g = jax.tree.map(described, pipe.g)
    batch = [jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)] * 3
    text = pipe._micro_value_and_grad.lower(params, g, *batch).compile() \
        .as_text()
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    under = re.compile(r"(^|/)(\w+\()*agg\)*/")
    assert names and all(under.search(n) for n in names), names
    assert any(n.split("/")[1].startswith("transpose(") for n in names)
    assert any(n.split("/")[1].startswith("jvp(") for n in names)
