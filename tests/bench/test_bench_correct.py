"""The comparison that decides ``correct``, at a size a test run holds.

A sound run through the harness (the look for a chip skipped, every
other part of a run driven) comes out correct; the control, the
reference computed in bfloat16 put in the program's place, and each
fault planted under the timed path come out not correct under each
cell's own limits.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench import check, harness
from bench.loops import closed_train
from bench.manifest import ROOT, Manifest

TINY = dict(n_users=300, n_items=200, n_edges=3000, embed_dim=16,
            n_layers=2, bpr_batch=256, base_batch=16)
CELLS = ("lightgcn-m25-train", "lightgcn-m25-ring4-train")
SEED = 2**31 + 11


def tiny(cell: str) -> dict:
    man = Manifest()
    cfg = man.config(man.cell(cell)["config"])
    cfg.update(TINY, mesh_shape=[1])
    return cfg


def run(cell="lightgcn-m25-train", seed=SEED, trace=False):
    return harness.run_cell(cell, seed, 0.05, trace, cfg=tiny(cell),
                            require_tpu=False)


def test_sound_run_is_correct():
    res = run()
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_step_s", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert res["device"]["platform"] == "cpu"
    assert res["checks"]["bad_rows"] == {"value": 0, "limit": 0}
    assert res["checks"]["skewed_batches"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(cell):
    cfg = tiny(cell)
    ref = Manifest().reference(cfg["model"])
    graph = closed_train.run_graph(cfg, SEED, None)
    rng = __import__("numpy").random.default_rng(0)
    idx = rng.permutation(graph.n_edges)[:3 * cfg["bpr_batch"]]
    batches = [(graph.user[i], graph.item[i],
                rng.integers(0, graph.n_items, len(i)).astype("int32"))
               for i in idx.reshape(3, -1)]
    f32 = closed_train.reference_readings(ref, cfg, graph, SEED, batches)
    bf16 = closed_train.reference_readings(ref, cfg, graph, SEED, batches,
                                           dtype=jnp.bfloat16)
    ok, shown = check.verdict(check.compare(bf16, f32),
                              Manifest().limits(cell))
    assert not ok, shown


def _unchanged_state(monkeypatch):
    from repro.pipeline.engine import Pipeline
    real = Pipeline.step_fn

    def stuck(self, state, step):
        return state, real(self, state, step)[1]
    monkeypatch.setattr(Pipeline, "step_fn", stuck)


def _half_batch(monkeypatch):
    from repro.core import bpr
    real = bpr.bpr_loss

    def half(user_e, item_e, users, pos, neg, l2=1e-4):
        k = users.shape[0] // 2
        return real(user_e, item_e, users[:k], pos[:k], neg[:k], l2=l2)
    monkeypatch.setattr(bpr, "bpr_loss", half)


def _altered_loss(monkeypatch):
    from repro.pipeline.engine import Pipeline
    real = Pipeline.step_fn

    def altered(self, state, step):
        state, loss = real(self, state, step)
        return state, loss * (1 + 1e-3)
    monkeypatch.setattr(Pipeline, "step_fn", altered)


def _biased_positives(monkeypatch):
    """The loader takes the graph's edges in their stored order."""
    from repro.data.loader import EdgeLoader
    import numpy as np
    monkeypatch.setattr(EdgeLoader, "_epoch_perm",
                        lambda self, epoch: np.arange(len(self.user))[
                            self.shard_id::self.num_shards])


def _biased_negatives(monkeypatch):
    """Negatives drawn from the first half of the items only."""
    from repro.pipeline.engine import Pipeline
    real = Pipeline._next_target_batch

    def low(self, k, step):
        users, pos, neg = real(self, k, step)
        return users, pos, neg % max(1, self.n_items // 2)
    monkeypatch.setattr(Pipeline, "_next_target_batch", low)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_loss, _biased_positives,
                                   _biased_negatives],
                         ids=["unchanged_state", "half_batch",
                              "altered_loss", "biased_positives",
                              "biased_negatives"])
def test_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run()
    assert not res["correct"], res["checks"]


RING_SCRIPT = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import harness
from bench.manifest import Manifest
man = Manifest()
cfg = man.config("lightgcn-m25-ring4")
cfg.update({tiny!r})
out = {{}}
out["sound"] = harness.run_cell("lightgcn-m25-ring4-train", {seed}, 0.05,
                               False, cfg=cfg, require_tpu=False)["correct"]
jax.lax.ppermute = lambda x, axis_name, perm: x
out["no_exchange"] = harness.run_cell("lightgcn-m25-ring4-train", {seed},
                                     0.05, False, cfg=cfg,
                                     require_tpu=False)["correct"]
print(json.dumps(out))
"""


def test_ring_without_its_exchange_is_not_correct():
    """Four virtual CPU devices: the sound ring run is correct, the same
    run with every collective-permute made the identity is not."""
    script = RING_SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                                tiny=TINY, seed=SEED)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False}


def test_no_accelerator_exits_nonzero_with_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lightgcn-m25-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr
