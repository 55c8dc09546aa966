"""Closed-loop full-graph training: ``Run.step`` again and again.

Mix keys (``bench/mixes/<name>.json``): ``loop`` (this file's name),
``compared_steps`` (the steps of set-up that the reference replays) and
``why``; any other key is refused.

Set-up builds one object, the program's ``Run`` with its compiled step
and state, from the seed: the graph from ``bench/synth.py``, the spec
from the configuration, the weights from the reference's ``init_params``
in one jitted call on the device.  It drives that ``Run`` through its
first ``compared_steps`` steps with ``Run.step`` (the first compiles),
recording each step's batch, its loss, the Adam state after the first
step and the parameters after the last, and hands the same ``Run`` to
the window.  The window repeats ``Run.step`` until ``--seconds`` have
passed and ends at ``block_until_ready`` on the state.  Once it has
closed and the program is freed, the reference replays the recorded
batches from the same weights (``bench/check.py`` compares).
"""
from __future__ import annotations

import time

import numpy as np

from bench import check, counts, synth
from bench.trace_reduce import STEP_SPAN

KEYS = {"loop", "compared_steps", "why"}


def validate(mix: dict) -> None:
    """Refuse a mix this loop does not implement."""
    extra = set(mix) - KEYS
    if extra:
        raise ValueError(f"closed_train mixes take {sorted(KEYS)}, "
                         f"not {sorted(extra)}")
    if int(mix["compared_steps"]) < 1:
        raise ValueError("compared_steps must be at least 1")


def run_graph(cfg: dict, seed: int, cache_dir: str | None) -> synth.Graph:
    """The configuration's graph relabeled by the run's seed."""
    chips = int(np.prod(cfg["mesh_shape"]))
    return synth.relabel(synth.base_graph(cfg, cache_dir), seed, chips)


def weights_key(seed: int):
    """A JAX key from a seed of any size (the low 32 bits key it, the
    rest is folded in)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def spec_for(cfg: dict, seed: int):
    """The program's ``ExperimentSpec`` for a configuration."""
    from repro.api.spec import ExperimentSpec
    chips = int(np.prod(cfg["mesh_shape"]))
    over = {
        "name": cfg["name"], "seed": seed,
        "model.arch": cfg["model"], "model.embed_dim": cfg["embed_dim"],
        "model.n_layers": cfg["n_layers"],
        "data.source": "bipartite", "data.n_users": cfg["n_users"],
        "data.n_items": cfg["n_items"], "data.edges": cfg["n_edges"],
        "data.test_frac": 0.0, "data.seed": seed,
        "plan.target_batch": cfg["bpr_batch"],
        "plan.base_batch": cfg["base_batch"],
        "plan.microbatch": cfg["bpr_batch"] // chips,
        "plan.warmup_epochs": cfg["warmup_epochs"],
        "optimizer": cfg["optimizer"], "base_lr": cfg["base_lr"],
        "l2": cfg["l2"],
    }
    if chips > 1:
        over.update({"mesh.shape": tuple(cfg["mesh_shape"]),
                     "mesh.axes": tuple(cfg["mesh_axes"])})
    return ExperimentSpec().override(over)


def reference_readings(ref, cfg, graph, seed, batches, **kw) -> dict:
    import jax
    g = ref.RefGraph(graph.user, graph.item, graph.n_users, graph.n_items)
    params0 = jax.jit(lambda k: ref.init_params(k, cfg))(weights_key(seed))
    return ref.train(cfg, g, params0, batches, **kw)


class Program:
    """The system under test, built and driven through its first steps."""

    def __init__(self, cfg: dict, graph: synth.Graph, seed: int, ref,
                 phases: dict):
        import jax
        from repro.api import build
        from repro.data.synth import InteractionData

        self.cfg, self.seed, self.ref = cfg, seed, ref
        t = time.perf_counter()
        self.run = build(spec_for(cfg, seed), train=InteractionData(
            graph.user, graph.item, graph.n_users, graph.n_items))
        jax.block_until_ready(self.run.state)
        phases["build"] = time.perf_counter() - t

        t = time.perf_counter()
        params = self.initial_params()
        pipe = self.run.pipeline
        self.run.state = pipe.apply_plan(
            {"params": params, "opt": pipe.opt.init(params)})
        jax.block_until_ready(self.run.state)
        phases["weights"] = time.perf_counter() - t

    def initial_params(self):
        import jax
        return jax.jit(lambda k: self.ref.init_params(k, self.cfg))(
            weights_key(self.seed))

    @property
    def route(self) -> str:
        return self.run.pipeline.g.spmm

    def first_steps(self, n: int, phases: dict) -> dict:
        """Steps 1..n through ``Run.step``, recording each batch as it
        enters the step, each loss, the first step's gradient (from the
        Adam state) and the change of the parameters after step n.  The
        batches are read where the engine draws them
        (``Pipeline._next_target_batch``, the one place a step's rows
        can be seen; a rename fails here, loudly)."""
        import jax
        import jax.numpy as jnp

        pipe = self.run.pipeline
        batches = []
        draw = pipe._next_target_batch

        def recorded(k, step):
            b = draw(k, step)
            batches.append(tuple(np.array(a) for a in b))
            return b

        pipe._next_target_batch = recorded
        losses, grad_norms = [], None
        t = time.perf_counter()
        try:
            for s in range(n):
                losses.append(self.run.step())
                if s == 0:
                    m = self.run.state["opt"]["m"]
                    b1 = self.cfg["adam_b1"]
                    grad_norms = {k: float(jnp.linalg.norm(v.ravel()))
                                  / (1 - b1) for k, v in m.items()}
                    phases["warmup_step"] = time.perf_counter() - t
                    t = time.perf_counter()
        finally:
            del pipe._next_target_batch
        p0 = self.initial_params()
        delta = {k: float(jnp.linalg.norm(
                     (v - jax.device_put(p0[k], v.sharding)).ravel()))
                 for k, v in self.run.state["params"].items()}
        phases["compared_steps"] = time.perf_counter() - t
        return {"batches": batches, "losses": losses,
                "grad_norms": grad_norms, "delta_norms": delta}


class Loop:
    """One run of a closed training mix: set-up in the constructor, then
    ``window``, ``release`` and ``check``."""

    def __init__(self, mix: dict, cfg: dict, ref, seed: int, *,
                 phases: dict, cache_dir: str | None, require_tpu: bool,
                 log=print):
        validate(mix)
        self.cfg, self.ref, self.seed = cfg, ref, seed
        t = time.perf_counter()
        self.graph = run_graph(cfg, seed, cache_dir)
        phases["generate"] = time.perf_counter() - t
        g = self.graph
        log(f"graph: {g.n_users} users x {g.n_items} items, "
            f"{g.n_edges} edges")
        self.prog = Program(cfg, g, seed, ref, phases)
        self.route = self.prog.route
        if require_tpu and self.route != cfg["route"]:
            raise RuntimeError(f"route {self.route!r}, the configuration "
                               f"states {cfg['route']!r}")
        log(f"route {self.route}, spmm edge-row bytes per call "
            f"{counts.spmm_edge_row_bytes(g.n_edges, cfg['embed_dim'])}")
        self.first = self.prog.first_steps(int(mix["compared_steps"]),
                                           phases)
        self.losses = list(self.first["losses"])

    def window(self, seconds: float, annotate: bool) -> dict:
        """Steps until ``seconds`` have passed; the last one begins
        inside them.  Host clock, ended by ``block_until_ready``."""
        import jax
        run, n = self.prog.run, 0
        t0 = time.perf_counter()
        while not n or time.perf_counter() - t0 < seconds:
            if annotate:
                with jax.profiler.StepTraceAnnotation(
                        STEP_SPAN, step_num=run.step_count):
                    self.losses.append(run.step())
            else:
                self.losses.append(run.step())
            n += 1
        jax.block_until_ready(run.state)
        return {"steps": n, "seconds": time.perf_counter() - t0}

    @property
    def attempted(self) -> int:
        return len(self.losses)

    @property
    def failed(self) -> int:
        return sum(not np.isfinite(x) for x in self.losses)

    def release(self) -> None:
        """Free the program and its state before the reference runs."""
        del self.prog

    def counts(self) -> dict:
        """Counts of a step that per-layer metrics read."""
        g, cfg = self.graph, self.cfg
        shape = dict(n_users=g.n_users, n_items=g.n_items,
                     n_edges=g.n_edges, d=cfg["embed_dim"],
                     n_layers=cfg["n_layers"])
        return {"spmm_step_bytes": counts.spmm_step_bytes(**shape),
                "step_flops": counts.step_flops(**shape,
                                                batch=cfg["bpr_batch"])}

    def check(self, log=print) -> tuple[dict, dict]:
        """(numbers, the limits this loop fixes itself): the training
        comparison with the reference, and the rows of the compared
        batches (``bench/check.py``)."""
        g, first = self.graph, self.first
        t = time.perf_counter()
        refd = reference_readings(self.ref, self.cfg, g, self.seed,
                                  first["batches"])
        numbers = check.compare(first, refd)
        numbers["bad_rows"] = check.bad_rows(
            first["batches"], g.user, g.item, g.n_items,
            self.cfg["bpr_batch"])
        numbers["skewed_batches"] = check.skewed_batches(
            first["batches"], g.user, g.item, g.n_items)
        log(f"reference: {time.perf_counter() - t:.3f} s; losses program "
            f"{first['losses']} reference {refd['losses']}")
        return numbers, {"bad_rows": 0, "skewed_batches": 0}
