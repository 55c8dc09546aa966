"""End-to-end training launcher.

CPU-sized by default (smoke-scale synthetic data); the same entry point
drives the production mesh on real hardware via --mesh.

GNNRecSys archs (lightgcn / ngcf / gcn) run through the unified
Experiment API (``repro.api``): every flag is a declarative-spec
override, so the CLI, a preset, and a JSON spec file all build the same
``ExperimentSpec`` — tiered-memory placement, the §7.1 large-batch
schedule, microbatched gradient accumulation, and streaming held-out
eval all ride along.

  python -m repro.launch.train --arch lightgcn --steps 100
  python -m repro.launch.train --arch ngcf --target-batch 4096 --microbatch 512
  python -m repro.launch.train --preset lightgcn-smoke
  python -m repro.launch.train --arch lightgcn --dataset gowalla --edges 8000
  python -m repro.launch.train --spec my_experiment.json --set plan.microbatch=128
  python -m repro.launch.train --arch gcn-cora --steps 50      # legacy archs

Sharded execution (mesh-parallel full-graph training; CPU CI uses
XLA_FLAGS=--xla_force_host_platform_device_count=4):

  python -m repro.launch.train --arch lightgcn --mesh 4
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as config_registry
from repro.api import (DataCfg, Experiment, ExperimentSpec, LoopCfg,
                       ModelCfg, PlanCfg, get_preset)
from repro.optim import adam
from repro.runtime.compile_cache import configure_compile_cache
from repro.runtime.loop import LoopConfig, run_training

PIPELINE_ARCHS = ("lightgcn", "ngcf", "gcn")

DEFAULT_CKPT_ROOT = "/tmp/repro_ckpt"


def default_spec() -> ExperimentSpec:
    """The launcher's base spec — the values the flags override."""
    return ExperimentSpec(
        name="train",
        model=ModelCfg(arch="lightgcn", embed_dim=32, n_layers=2),
        data=DataCfg(source="synth", dataset="movielens-10m", edges=4000),
        plan=PlanCfg(base_batch=512, target_batch=2048, microbatch=512),
        loop=LoopCfg(steps=100),
    )


def build_arg_parser() -> argparse.ArgumentParser:
    """Flags default to None so only explicitly-passed ones override the
    base spec (preset / spec file / ``default_spec``)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", help="model architecture "
                    f"(pipeline: {', '.join(PIPELINE_ARCHS)}; plus the "
                    "legacy CPU trainers)")
    ap.add_argument("--preset", help="start from a named spec "
                    "(repro.api.preset_names())")
    ap.add_argument("--spec", help="start from a JSON ExperimentSpec file")
    ap.add_argument("--set", action="append", default=[], metavar="PATH=V",
                    help="dotted spec override, e.g. plan.hbm_budget=2048 "
                         "(repeatable; values parsed as JSON)")
    ap.add_argument("--steps", type=int)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--dataset", help="paper dataset statistics to "
                    "synthesize (repro.data.synth.DATASET_STATS)")
    ap.add_argument("--edges", type=int)
    ap.add_argument("--target-batch", type=int,
                    help="large-batch target (accumulated microbatches)")
    ap.add_argument("--microbatch", type=int,
                    help="microbatch size; 0 = derive from HBM headroom")
    ap.add_argument("--embed-dim", type=int)
    ap.add_argument("--layers", type=int)
    ap.add_argument("--eval-every", type=int,
                    help="held-out streaming-eval cadence in steps; "
                         "0 = final eval only")
    ap.add_argument("--eval-k", type=int)
    ap.add_argument("--mesh", help="mesh shape for sharded execution, "
                    "e.g. '4' or '2x2' (spec override mesh.shape); on CPU "
                    "pair with XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=N")
    ap.add_argument("--spmm", choices=["auto", "ring"],
                    help="aggregation dispatch (mesh.spmm); 'ring' forces "
                         "the ring route even on one device")
    ap.add_argument("--memory-topology",
                    help="registered TierTopology to model "
                         "(repro.memory.topology_names(), e.g. "
                         "dram-optane-appdirect; spec override "
                         "memory.topology)")
    ap.add_argument("--placement-policy",
                    help="registered placement policy "
                         "(repro.memory.policy_names(), e.g. paper-recipe; "
                         "spec override memory.policy)")
    ap.add_argument("--pin", action="append", default=[],
                    metavar="TENSOR=TIER",
                    help="pin a tensor (by profile name or substring) to a "
                         "tier, e.g. --pin item_embed=slow (repeatable; "
                         "merges into memory.pins)")
    ap.add_argument("--compress-grads", choices=["none", "int8", "topk"],
                    help="compressed gradient combine (compression.grads): "
                         "int8 stochastic psum or top-k all-gather, with "
                         "error feedback")
    ap.add_argument("--compress-frac", type=float,
                    help="top-k kept fraction (compression.frac)")
    ap.add_argument("--embed-store", choices=["fp32", "int8"],
                    help="capacity-tier embedding-table storage "
                         "(compression.embed_store): int8 = ~1/4 bytes, "
                         "fp32 dequant-on-gather")
    return ap


def _parse_set(entry: str) -> tuple[str, object]:
    path, sep, raw = entry.partition("=")
    if not sep:
        raise SystemExit(f"--set expects PATH=VALUE, got {entry!r}")
    try:
        value = json.loads(raw)
    except json.JSONDecodeError:
        value = raw                       # bare strings pass through
    return path.strip(), value


def spec_from_args(args: argparse.Namespace) -> ExperimentSpec:
    """argparse namespace -> ExperimentSpec: base (spec file > preset >
    defaults), then flag overrides, then --set dotted overrides."""
    if args.spec:
        spec = ExperimentSpec.from_file(args.spec)
    elif args.preset:
        spec = get_preset(args.preset)
    else:
        spec = default_spec()
    ov: dict[str, object] = {}
    if args.arch is not None:
        ov["model.arch"] = args.arch
    if args.embed_dim is not None:
        ov["model.embed_dim"] = args.embed_dim
    if args.layers is not None:
        ov["model.n_layers"] = args.layers
    if args.dataset is not None:
        ov["data.dataset"] = args.dataset
    if args.edges is not None:
        ov["data.edges"] = args.edges
    if args.target_batch is not None:
        ov["plan.target_batch"] = args.target_batch
    if args.microbatch is not None:
        ov["plan.microbatch"] = args.microbatch or None
    if args.steps is not None:
        ov["loop.steps"] = args.steps
    if args.eval_every is not None:
        ov["loop.eval_every"] = args.eval_every or None
    if args.eval_k is not None:
        ov["eval.k"] = args.eval_k
    if args.mesh is not None:
        from repro.pipeline.shard import parse_mesh
        ov["mesh.shape"] = parse_mesh(args.mesh)
    if args.spmm is not None:
        ov["mesh.spmm"] = None if args.spmm == "auto" else args.spmm
    if args.memory_topology is not None:
        ov["memory.topology"] = args.memory_topology
    if args.placement_policy is not None:
        ov["memory.policy"] = args.placement_policy
    if args.compress_grads is not None:
        ov["compression.grads"] = args.compress_grads
    if args.compress_frac is not None:
        ov["compression.frac"] = args.compress_frac
    if args.embed_store is not None:
        ov["compression.embed_store"] = args.embed_store
    if args.pin:
        pins = dict(spec.memory.pins or {})
        for entry in args.pin:
            name, sep, tier = entry.partition("=")
            if not sep:
                raise SystemExit(f"--pin expects TENSOR=TIER, got {entry!r}")
            pins[name.strip()] = tier.strip()
        ov["memory.pins"] = pins
    spec = spec.override(ov)
    spec = spec.override(dict(_parse_set(s) for s in args.set))
    # ckpt-dir default last, so it names the arch the run actually uses
    # (a --set model.arch=... override included)
    if spec.loop.ckpt_dir is None:
        ckpt_root = args.ckpt_dir if args.ckpt_dir is not None \
            else DEFAULT_CKPT_ROOT
        spec = spec.override({"loop.ckpt_dir": f"{ckpt_root}/{spec.model.arch}"})
    return spec


def run_experiment(spec: ExperimentSpec):
    """One spec, end to end: build -> fit (fault-tolerant loop, resumes
    from the spec's checkpoint dir) -> final held-out streaming eval."""
    run = Experiment(spec).build()
    print(run.describe())
    t0 = time.perf_counter()
    report = run.fit()
    dt = time.perf_counter() - t0
    pipe = run.pipeline
    print(f"[{spec.model.arch}] {report.steps_run} steps in {dt:.1f}s "
          f"loss {_loss_span(report)} "
          f"(microbatch={pipe.plan.microbatch}, "
          f"accum={pipe.plan.microbatches_for_epoch(pipe.loader.state.epoch)}x, "
          f"resumed_from={report.resumed_from})")
    for step, m in report.eval_history:
        print(f"  eval@{step}: {_fmt_metrics(m)}")
    if run.holdout is not None:
        print(f"[{spec.model.arch}] final held-out: "
              f"{_fmt_metrics(run.evaluate())}")
    return report


def _fmt_metrics(m: dict) -> str:
    return " ".join(f"{k}={v:.4f}" for k, v in sorted(m.items()))


def _loss_span(report) -> str:
    """'first -> last' loss, robust to a resume at max_steps (no new
    steps run -> losses is empty)."""
    if not report.losses:
        return "n/a (already at max_steps)"
    return f"{report.losses[0]:.4f} -> {report.losses[-1]:.4f}"


def train_gcn(steps: int, ckpt_dir: str):
    from repro.core.graph import from_numpy
    from repro.models import gcn
    cfg = config_registry.get("gcn_cora").SMOKE
    rng = np.random.default_rng(0)
    n = 200
    src = rng.integers(0, n, 1600).astype(np.int32)
    dst = rng.integers(0, n, 1600).astype(np.int32)
    g = from_numpy(src, dst, n)
    x = jnp.asarray(rng.standard_normal((n, cfg.d_feat)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, cfg.n_classes, n).astype(np.int32))
    lmask = jnp.ones((n,), jnp.float32)
    params = gcn.init_params(cfg, jax.random.PRNGKey(0))
    opt = adam(1e-2)

    @jax.jit
    def train_step(state):
        loss, grads = jax.value_and_grad(
            lambda p: gcn.loss_fn(cfg, p, g, x, labels, lmask))(state["params"])
        p, o = opt.update(grads, state["opt"], state["params"])
        return {"params": p, "opt": o}, loss

    state0 = {"params": params, "opt": opt.init(params)}
    report = run_training(
        LoopConfig(ckpt_dir=ckpt_dir, ckpt_every=max(steps // 2, 1),
                   max_steps=steps, async_ckpt=False),
        state0, lambda s, _: train_step(s))
    print(f"[gcn] loss {_loss_span(report)}")
    return report


def train_recsys(arch: str, steps: int, ckpt_dir: str, batch: int = 256):
    from repro.models import recsys_models as rm
    mod = config_registry.get(arch)
    cfg = mod.SMOKE
    rng = np.random.default_rng(0)
    init = {"deepfm": rm.deepfm_init, "xdeepfm": rm.xdeepfm_init,
            "dlrm_rm2": rm.dlrm_init}[config_registry.canon(arch)]
    fwd = {"deepfm": rm.deepfm_forward, "xdeepfm": rm.xdeepfm_forward,
           "dlrm_rm2": rm.dlrm_forward}[config_registry.canon(arch)]
    params = init(cfg, jax.random.PRNGKey(0))
    opt = adam(1e-3)
    is_dlrm = config_registry.canon(arch) == "dlrm_rm2"

    @jax.jit
    def train_step(state, *args):
        *feats, labels = args

        def loss_fn(p):
            return rm.bce_loss(fwd(cfg, p, *feats), labels)

        loss, grads = jax.value_and_grad(loss_fn)(state["params"])
        p, o = opt.update(grads, state["opt"], state["params"])
        return {"params": p, "opt": o}, loss

    def step_fn(state, step):
        ids = jnp.asarray(rng.integers(0, cfg.vocab, (batch, cfg.n_sparse))
                          .astype(np.int32))
        labels = jnp.asarray(rng.integers(0, 2, batch).astype(np.float32))
        if is_dlrm:
            dense = jnp.asarray(rng.standard_normal((batch, cfg.n_dense))
                                .astype(np.float32))
            return train_step(state, dense, ids, labels)
        return train_step(state, ids, labels)

    state0 = {"params": params, "opt": opt.init(params)}
    report = run_training(
        LoopConfig(ckpt_dir=ckpt_dir, ckpt_every=max(steps // 2, 1),
                   max_steps=steps, async_ckpt=False), state0, step_fn)
    print(f"[{arch}] loss {_loss_span(report)}")
    return report


def main():
    args = build_arg_parser().parse_args()
    configure_compile_cache()
    if args.preset or args.spec or args.arch in PIPELINE_ARCHS:
        run_experiment(spec_from_args(args))
        return
    if args.arch is None:
        raise SystemExit("need --arch, --preset, or --spec")
    arch = config_registry.canon(args.arch)
    steps = args.steps if args.steps is not None else 100
    ckpt_root = args.ckpt_dir if args.ckpt_dir is not None \
        else DEFAULT_CKPT_ROOT
    if arch == "gcn_cora":
        train_gcn(steps, f"{ckpt_root}/{arch}")
    elif arch in ("deepfm", "xdeepfm", "dlrm_rm2"):
        train_recsys(arch, steps, f"{ckpt_root}/{arch}")
    else:
        raise SystemExit(
            f"CPU trainer for {arch!r} not wired; pipeline archs: "
            f"{', '.join(PIPELINE_ARCHS)}; also gcn-cora, deepfm, xdeepfm, "
            f"dlrm_rm2 (LM archs run via the dry-run)")


if __name__ == "__main__":
    main()
