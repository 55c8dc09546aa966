"""Layer 2 — HLO invariant auditor (imports JAX; import explicitly).

The AST layer can't see what XLA actually emits, and the paper's whole
§5-§6 argument is about where bytes live and move — so this layer
lowers the jitted train step and the serve path for representative
presets and asserts on the lowered text itself:

  * **no f64 ops** anywhere in a hot-path lowering (a silent dtype
    widening doubles every byte the paper counts);
  * **no host transfers inside the step** (device→host custom calls /
    host memory spaces — MTrainS/RecNMP-style wins evaporate from one
    accidental sync);
  * **collectives present/absent exactly per MeshCfg/CompressionCfg**
    via the declarative ``FRAGMENTS`` table below — the one source of
    truth the former one-off string asserts in ``test_compression.py``
    and ``test_distributed.py`` now share;
  * a **recompile-hazard count**: the microbatch schedule must trace to
    ONE chunk shape (warm-up epochs change the accumulation factor,
    never the chunk shape), or every epoch boundary recompiles.

Pure functions over lowered text plus small drivers that build a run
from an ``ExperimentSpec`` — used by ``tools/lint.py --hlo``
(``make audit``) and by the test suite.
"""
from __future__ import annotations

import dataclasses
import math
import re

__all__ = ["HloExpectation", "COLLECTIVES", "FRAGMENTS", "expect",
           "expectation_for", "check_text", "assert_clean",
           "lower_train_step", "lower_serve", "recompile_hazard",
           "message_shape", "ngcf_message_fragment", "fusion_audit",
           "audit_spec", "smoke_audit"]

# every collective op name XLA can lower for this repo's programs; the
# single-device expectation is their total absence
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter")

_F64_RE = re.compile(r"\b(f64|c128)\[")
# device→host movement markers: host-offload custom calls, placement
# annotations, and the host memory-space color in buffer annotations
_HOST_RES = (re.compile(r"MoveToHost|MoveToDevice"),
             re.compile(r"annotate_device_placement"),
             re.compile(r"S\(5\)"))


@dataclasses.dataclass(frozen=True)
class HloExpectation:
    """What a lowered program must (not) contain, by substring."""
    name: str
    contains: tuple[str, ...] = ()
    absent: tuple[str, ...] = ()

    def merged(self, other: "HloExpectation") -> "HloExpectation":
        contains = self.contains + tuple(
            c for c in other.contains if c not in self.contains)
        # a substring any fragment requires can't simultaneously be
        # forbidden: contains wins (int8 psum adds all-reduce to a
        # config whose base fragment forbids nothing it needs)
        absent = tuple(a for a in self.absent + other.absent
                       if a not in contains)
        absent = tuple(dict.fromkeys(absent))
        return HloExpectation(f"{self.name}+{other.name}",
                              tuple(c for c in contains
                                    if c not in absent), absent)


# ------------------------------------------------------------------ table
# The declarative expectation table: one named fragment per collective
# contract in the codebase.  Tests and the auditor compose these with
# ``expect(...)`` / ``expectation_for(...)`` instead of hand-rolling
# string asserts.
FRAGMENTS = {
    # no mesh -> no collectives of any kind in the lowering
    "single-device": HloExpectation("single-device", absent=COLLECTIVES),
    # ring SpMM rotates blocks with collective-permute (GSPMD may still
    # emit all-gathers elsewhere in the step, e.g. for the BPR row
    # gather out of the row-sharded tables — the ring contract is only
    # that the permute is present)
    "ring-spmm": HloExpectation("ring-spmm",
                                contains=("collective-permute",)),
    # sharded training psums grads with a plain all-reduce
    "grad-psum": HloExpectation("grad-psum", contains=("all-reduce",)),
    # int8 gradient combine: a REAL integer all-reduce (int8 payload,
    # int32 accumulate) — test_compression's former one-off assert
    "grad-combine@int8": HloExpectation("grad-combine@int8",
                                        contains=("all-reduce", "s32")),
    # top-k combine exchanges sparse shares via all-gather, no psum of
    # the dense gradient
    "grad-combine@topk": HloExpectation("grad-combine@topk",
                                        contains=("all-gather",)),
}


def message_shape(n_edges: int, embed_dim: int) -> str:
    """The [E, D] message buffer's shape string as XLA prints it — the
    needle the fused-NGCF fragments look for."""
    return f"f32[{n_edges},{embed_dim}]"


def ngcf_message_fragment(n_edges: int, embed_dim: int, *,
                          fused: bool) -> HloExpectation:
    """The graph-shaped half of the fused-NGCF contract, built per run
    (FRAGMENTS entries are static; the message shape is not).  The
    COMPOSED lowering must contain the [E, D] message buffer (it
    materializes one per layer); the FUSED Pallas lowering must not
    contain it at all.  The fused XLA fallback still gathers operand
    rows at that shape, so its invariant is the relative count in
    ``fusion_audit``, not this absolute fragment."""
    shape = message_shape(n_edges, embed_dim)
    if fused:
        return HloExpectation("ngcf-fused-messages", absent=(shape,))
    return HloExpectation("ngcf-composed-messages", contains=(shape,))


def expect(*names: str) -> HloExpectation:
    """Merge named ``FRAGMENTS`` into one expectation."""
    exp = FRAGMENTS[names[0]]
    for n in names[1:]:
        exp = exp.merged(FRAGMENTS[n])
    return exp


def expectation_for(*, n_shards: int = 1,
                    grads: str = "none") -> HloExpectation:
    """The full train-step expectation for a (MeshCfg, CompressionCfg)
    point: which fragments apply is a pure function of the config."""
    if n_shards <= 1:
        return expect("single-device")
    names = ["ring-spmm"]
    if grads == "topk":
        names.append("grad-combine@topk")
    elif grads == "int8":
        names.append("grad-combine@int8")
    else:
        names.append("grad-psum")
    return expect(*names)


# ------------------------------------------------------------------ checks
def check_text(txt: str, expectation: HloExpectation | None = None, *,
               forbid_f64: bool = True, forbid_host_transfer: bool = True,
               where: str = "") -> list[str]:
    """Audit one lowered (compiled) HLO text; returns violations."""
    out = []
    tag = f"[{where}] " if where else ""
    if forbid_f64:
        m = _F64_RE.search(txt)
        if m:
            out.append(f"{tag}f64 op in lowering ({m.group(0)}...): a "
                       "hot path widened past fp32")
    if forbid_host_transfer:
        for pat in _HOST_RES:
            m = pat.search(txt)
            if m:
                out.append(f"{tag}host-transfer marker "
                           f"{m.group(0)!r} inside the step lowering")
    if expectation is not None:
        for s in expectation.contains:
            if s not in txt:
                out.append(f"{tag}expected collective {s!r} missing "
                           f"(expectation {expectation.name})")
        for s in expectation.absent:
            if s in txt:
                out.append(f"{tag}forbidden op {s!r} present "
                           f"(expectation {expectation.name})")
    return out


def assert_clean(txt: str, expectation: HloExpectation | None = None,
                 **kw) -> None:
    """``check_text`` raising AssertionError with every violation — the
    one-call form the test suite uses."""
    violations = check_text(txt, expectation, **kw)
    assert not violations, "; ".join(violations)


# ----------------------------------------------------------------- drivers
def lower_train_step(run) -> dict[str, str]:
    """Compiled HLO texts of the two jitted halves of one engine step
    (the microbatch value-and-grad and the optimizer update) for a
    ``repro.api.Run``, lowered exactly as ``step_fn`` would execute
    them (under the run's sharding hints)."""
    import jax.numpy as jnp
    pipe = run.pipeline
    u, p, n = pipe._next_target_batch(1, 0)
    state = run.state
    with pipe.step_context():
        db = pipe._device_batch(u, p, n)
        micro = pipe._micro_value_and_grad.lower(
            state["params"], pipe.g, *db).compile().as_text()
        # params stand in for grads: same pytree, shapes, dtypes
        update = pipe._apply_update.lower(
            state, state["params"], jnp.float32(1e-3)).compile().as_text()
    return {"micro_step": micro, "apply_update": update}


def lower_serve(run, *, k: int = 10, item_block: int = 256,
                users: int = 8) -> dict[str, str]:
    """Compiled HLO of the fused serve oracle (the serving hot path's
    jitted score → mask → top-K sweep) on a host snapshot of the run's
    embeddings — serving scores a placed snapshot, not the (possibly
    mesh-sharded) live training arrays."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    ue, ie = run.embeddings()
    ue, ie = np.asarray(ue), np.asarray(ie)
    ue = jnp.asarray(ue)[:users]
    seen = jnp.zeros((ue.shape[0], 1), jnp.int32)
    mask = jnp.zeros((ue.shape[0], 1), bool)
    n_items = int(ie.shape[0])
    txt = ref.fused_topk_score_ref.lower(
        ue, jnp.asarray(ie), seen, mask, k=min(k, n_items),
        item_block=min(item_block, n_items),
        n_items=n_items).compile().as_text()
    return {"fused_serve": txt}


def recompile_hazard(plan, n_epochs: int = 8,
                     batches: list[int] | None = None) -> list[int]:
    """Distinct microbatch chunk shapes ``Pipeline.grads_for_batch``
    would trace across the schedule.  More than one distinct shape
    means an extra XLA compile per shape — the warm-up schedule must
    vary the accumulation COUNT, never the chunk shape.

    By default audits the engine's own feed (the loader-fed target
    batch, ``microbatches_for_epoch * global_microbatch`` per epoch —
    the round-up to whole microbatches IS the mitigation this check
    pins).  Pass ``batches`` to audit a direct ``grads_for_batch``
    caller's batch sizes instead: any size that is not a microbatch
    multiple shows up here as the ragged trailing chunk it would
    trace."""
    mu = plan.global_microbatch
    if batches is None:
        batches = [plan.microbatches_for_epoch(e) * mu
                   for e in range(n_epochs)]
    shapes = set()
    for n in batches:
        for c in range(max(1, math.ceil(n / mu))):
            shapes.add(min((c + 1) * mu, n) - c * mu)
    return sorted(shapes)


def audit_spec(spec, *, serve: bool = True, n_epochs: int = 8
               ) -> list[str]:
    """Build ``spec`` and audit every hot-path lowering: train halves
    (f64 / host-transfer / collectives per the spec's own MeshCfg +
    CompressionCfg), the fused serve path, and the recompile hazard.
    Returns all violations (empty = clean)."""
    from repro.api import build
    run = build(spec)
    n_shards = 1
    for d in spec.mesh.shape:
        n_shards *= int(d)
    exp = expectation_for(n_shards=n_shards, grads=spec.compression.grads)
    violations = []
    for name, txt in lower_train_step(run).items():
        # the collective contract binds the aggregation step; the
        # optimizer update only shares the f64/host invariants and, when
        # sharded, must not itself gather or widen anything
        e = exp if name == "micro_step" else (
            expect("single-device") if n_shards <= 1 else None)
        violations += check_text(txt, e, where=f"{spec.name}:{name}")
    if serve:
        for name, txt in lower_serve(run).items():
            violations += check_text(txt, expect("single-device"),
                                     where=f"{spec.name}:{name}")
    shapes = recompile_hazard(run.pipeline.plan, n_epochs=n_epochs)
    if len(shapes) != 1:
        violations.append(
            f"[{spec.name}:schedule] recompile hazard: {len(shapes)} "
            f"distinct microbatch chunk shapes {shapes} across the "
            "schedule (expected exactly 1)")
    return violations


def fusion_audit(spec, *, where: str = "") -> list[str]:
    """The fused-NGCF train-step contract, checked on the LOWERED text:
    build the same spec at ``model.hadamard`` 'fused' and 'composed',
    lower both micro steps, and require

      * the composed lowering CONTAINS the [E, D] message buffer (the
        absolute fragment — it materializes one per layer);
      * the fused lowering references that shape STRICTLY less often —
        on TPU the Pallas kernel drops it entirely, while the XLA
        fallback still gathers operand rows at [E, D] inside the
        aggregation, so the cross-arm count is the invariant that
        holds on every backend.
    """
    from repro.api import build
    txts, runs = {}, {}
    for had in ("fused", "composed"):
        s = spec.override({"model.hadamard": had,
                           "name": f"{spec.name}@{had}"})
        runs[had] = build(s)
        txts[had] = lower_train_step(runs[had])["micro_step"]
    g = runs["fused"].pipeline.g
    tag = f"[{where}] " if where else ""
    if g.hadamard_route != "fused":
        return [f"{tag}model.hadamard='fused' did not resolve to the "
                "fused route"]
    out = check_text(txts["composed"],
                     ngcf_message_fragment(g.n_edges, spec.model.embed_dim,
                                           fused=False),
                     where=f"{where}:composed")
    shape = message_shape(g.n_edges, spec.model.embed_dim)
    n_fused = txts["fused"].count(shape)
    n_composed = txts["composed"].count(shape)
    if n_fused >= n_composed:
        out.append(f"{tag}fused NGCF micro step references the message "
                   f"shape {shape} {n_fused}x vs composed "
                   f"{n_composed}x — the fusion bought nothing")
    return out


# ------------------------------------------------------------------ smoke
_SMOKE_OV = {"loop.steps": 5, "plan.target_batch": 64,
             "plan.microbatch": 16, "plan.warmup_epochs": 2,
             "data.edges": 1200, "loop.ckpt_dir": None}


def smoke_audit(mesh: int = 1, grads: str = "none",
                embed_store: str = "fp32", fused_serve: bool = True,
                arch: str = "lightgcn") -> list[str]:
    """The representative-preset audit ``make audit`` runs: the
    ``{arch}-smoke`` preset at a (mesh, compression) point.  ``mesh > 1``
    requires the caller to have forced that many devices (the CLI
    spawns a subprocess with ``XLA_FLAGS``).  The ngcf arch adds the
    fused-Hadamard contract: ``fusion_audit`` at mesh=1; at mesh>1 the
    audit asserts that 'auto' resolved to the factored Hadamard term,
    which rides the same symmetric ring as LightGCN (plus the standard
    ring collectives)."""
    from repro.api import get_preset
    ov = dict(_SMOKE_OV)
    if mesh > 1:
        ov.update({"mesh.shape": (mesh,), "plan.microbatch": 4})
    ov.update({"compression.grads": grads,
               "compression.embed_store": embed_store})
    spec = get_preset(f"{arch}-smoke").override(ov)
    name = f"{arch}-smoke[mesh={mesh},grads={grads},store={embed_store}]"
    spec = spec.override({"name": name})
    violations = audit_spec(spec, serve=fused_serve)
    if arch == "ngcf":
        if mesh <= 1:
            violations += fusion_audit(spec, where=name)
        else:
            from repro.api import build
            run = build(spec.override({"name": f"{name}@ring"}))
            route = run.pipeline.g.hadamard_route
            if route != "factored":
                violations.append(
                    f"[{name}] ring dispatch resolved model.hadamard="
                    f"'auto' to {route!r}, not the factored route")
    return violations
