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
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ann import ann_block_scores_pallas
from repro.kernels.hadamard_spmm import hadamard_spmm_pallas
from repro.kernels.spmm import spmm_csr_pallas
from repro.kernels.topk_score import fused_topk_score_pallas

N_USERS, N_ITEMS, D = 349_184, 53_248, 128
E = 1 << 20            # 4 MiB of int32 per index array: 4x the SMEM


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


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
