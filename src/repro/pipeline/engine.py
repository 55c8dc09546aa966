"""Unified training engine (paper §7 end-to-end).

Composes the previously-disconnected subsystems into one pipeline:

  TieredMemoryPlanner  — placement over the run's actual tensor set,
                         re-run on the loop's re-layout requests;
  LargeBatchSchedule   — per-epoch batch + LR (warm-up batch = target/10
                         for the first epochs, linear LR scaling);
  microbatch gradient accumulation — the target batch B runs as
                         ceil(B/microbatch) accumulated microbatches so
                         the paper's 150K-sample batches fit a fixed
                         HBM budget;
  kernel-routed models — registry forwards aggregate through the
                         Pallas/XLA SpMM dispatch (pipeline.sparse);
  EdgeLoader           — deterministic resumable microbatch stream;
  ShardPlan            — mesh-parallel execution (pipeline.shard): ring
                         SpMM aggregation, dp-sharded batch chunks with
                         GSPMD-psum'd grads, per-device planner budgets,
                         the whole step under dist.hints sharding hints
                         (``step_context``);
  runtime.loop         — the fault-tolerant outer loop consumes
                         ``step_fn``/``on_relayout``/``step_context``
                         produced here (see runtime.loop.run_pipeline).

The loader iterates at *microbatch* granularity; one engine step drains
``microbatches_for_epoch(epoch)`` consecutive microbatches, so the
warm-up epochs automatically accumulate fewer microbatches per update.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bpr
from repro.core.large_batch import LargeBatchSchedule
from repro.data.loader import EdgeLoader
from repro.data.synth import InteractionData
from repro.dist.hints import sharding_hints
from repro.memory import (TieredExecutor, get_topology, like_placement,
                          on_device)
from repro.optim import adam, sgd
from repro.pipeline.plan import TrainPlan, build_train_plan
from repro.pipeline.registry import get_model
from repro.pipeline.shard import ShardPlan
from repro.pipeline.sparse import BipartiteCSR, default_impl


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    arch: str = "lightgcn"
    embed_dim: int = 32
    n_layers: int = 2
    optimizer: str = "adam"            # 'adam' | 'sgd'
    base_lr: float = 1e-3
    base_batch: int = 256
    target_batch: int = 2048
    microbatch: int | None = None      # None -> derived from HBM headroom;
    #                                    per-SHARD when the mesh has P > 1
    warmup_epochs: int = 2
    lr_scaling: str = "linear"         # 'linear' | 'sqrt' (paper ablation)
    l2: float = 1e-4
    hbm_budget: int | None = None      # fast-tier budget override (bytes/device)
    impl: str | None = None            # kernel dispatch override; 'ring'
    #                                    forces the sharded aggregation route
    hadamard: str = "auto"             # NGCF Hadamard route: 'auto' |
    #                                    'fused' (no [E, D]) | 'composed'
    seed: int = 0
    # memory-tier subsystem (repro.memory): which registered topology
    # the run models, which placement policy assigns tensors to tiers,
    # per-tier capacity overrides, and name->tier pins.  The defaults
    # reproduce the pre-redesign planner bit for bit.
    memory_topology: str = "tpu-hbm-host"
    memory_policy: str = "greedy"
    memory_capacity: dict | None = None   # tier name -> bytes
    memory_pins: dict | None = None       # tensor (sub)name -> tier name
    # sharded execution (pipeline.shard.ShardPlan); the defaults are the
    # inert single-device plan — bit-identical to the unsharded pipeline
    mesh_shape: tuple[int, ...] = (1,)
    mesh_axes: tuple[str, ...] | None = None   # None -> auto axis names
    spmm: str | None = None            # None (auto) | 'ring'
    ring_steps: int | None = None      # banded ring band (n_steps < P)
    # byte compression on the slow links (repro.api.CompressionCfg ->
    # optim.compression): the gradient combine, capacity-tier embedding
    # storage, and the ring payload.  The 'none'/'fp32' defaults build
    # no compressor and stay bit-identical to the exact pipeline.
    grad_compression: str = "none"     # 'none' | 'int8' | 'topk'
    compression_frac: float = 0.01     # top-k kept fraction
    compression_ef: bool = True        # carry compression residuals
    embed_store: str = "fp32"          # 'fp32' | 'int8' slow-tier tables
    ring_compression: str = "none"     # 'none' | 'int8' ring payload
    # held-out streaming evaluation (repro.eval); cadence lives in the
    # loop's LoopConfig.eval_every — these shape one eval sweep
    eval_k: int = 20
    eval_user_batch: int | None = None  # None -> derived from HBM headroom
    eval_item_block: int = 1024


class Pipeline:
    """One training run: state, plan, and the step the loop executes."""

    def __init__(self, cfg: PipelineConfig, train: InteractionData,
                 holdout: InteractionData | None = None):
        self.cfg = cfg
        self.spec = get_model(cfg.arch)
        # one ShardPlan flows through every layer below; None = the
        # inert single-device path, bit-identical to the pre-shard
        # pipeline.  impl='ring' forces the ring route (BipartiteCSR
        # builds a degenerate 1-device plan when no mesh is configured).
        self.shard = ShardPlan.from_config(
            cfg.mesh_shape, cfg.mesh_axes, cfg.spmm, cfg.ring_steps,
            ring_quant=(cfg.ring_compression == "int8"))
        self.g = BipartiteCSR(train.user, train.item, train.n_users,
                              train.n_items, impl=cfg.impl, shard=self.shard,
                              hadamard=cfg.hadamard)
        self.shard = self.g.shard
        # ring cubes exist before the planner sizes them and before the
        # first trace, so the jitted step takes them as arguments
        self.g.build_ring(self.spec.ring_ops)
        impl = self.g.impl                     # kernel impl: pallas | xla
        self.n_items = train.n_items

        params = self.spec.init(jax.random.PRNGKey(cfg.seed), train.n_users,
                                train.n_items, cfg.embed_dim, cfg.n_layers)
        self.opt = {"adam": adam, "sgd": sgd}[cfg.optimizer](cfg.base_lr)
        opt_state = self.opt.init(params)

        sched = LargeBatchSchedule(base_lr=cfg.base_lr,
                                   base_batch=cfg.base_batch,
                                   target_batch=cfg.target_batch,
                                   warmup_epochs=cfg.warmup_epochs,
                                   scaling=cfg.lr_scaling)
        self.topology = get_topology(cfg.memory_topology) \
            .with_capacity(cfg.memory_capacity or {})
        self.plan = build_train_plan(cfg.arch, self.spec, params, opt_state,
                                     self.g, cfg.n_layers, cfg.embed_dim,
                                     sched, impl, hbm_budget=cfg.hbm_budget,
                                     microbatch=cfg.microbatch,
                                     shard=self.shard,
                                     topology=self.topology,
                                     policy=cfg.memory_policy,
                                     pins=cfg.memory_pins,
                                     embed_store=cfg.embed_store)
        self.executor = TieredExecutor(self.plan.plan,
                                       embed_store=cfg.embed_store)
        # compressed gradient combine (None = exact fp32, bit-identical
        # to the pre-compression step).  Its residual/key state rides
        # the training state under "comp": the executor's fetch/commit
        # only walk params/opt, and shard_state row-shards the stacked
        # [P, ...] residuals over the mesh like any other large table.
        self.compressor = None
        if cfg.grad_compression != "none":
            from repro.pipeline.compress import GradCompressor
            self.compressor = GradCompressor(
                cfg.grad_compression, cfg.compression_frac,
                cfg.compression_ef, shard=self.shard)
        state0 = {"params": params, "opt": opt_state}
        if self.compressor is not None:
            state0["comp"] = self.compressor.init_state(params, cfg.seed)
        self._state0 = self.apply_plan(state0)

        # the loader iterates at GLOBAL microbatch granularity: one
        # loader batch feeds all P shards (microbatch rows each)
        self.loader = EdgeLoader(train.user, train.item,
                                 batch=self.plan.global_microbatch,
                                 seed=cfg.seed)
        self._next_step = 0

        n_layers = cfg.n_layers
        l2 = cfg.l2
        spec = self.spec

        # the graph is an argument (a pytree of index arrays), never a
        # closed-over constant: programs stay small at millions of edges
        @jax.jit
        def micro_value_and_grad(params, g, users, pos, neg):
            params = on_device(params)       # memory-kind slow tier
            def loss_fn(p):
                ue, ie = spec.forward(p, g, n_layers)
                return bpr.bpr_loss(ue, ie, users, pos, neg, l2=l2)
            return jax.value_and_grad(loss_fn)(params)

        self._forward = jax.jit(
            lambda params, g: spec.forward(on_device(params), g, n_layers))

        compressor = self.compressor

        @jax.jit
        def apply_update(state, grads, lr):
            placed = state
            state = on_device(state)
            out = {}
            if compressor is not None:
                grads, out["comp"] = compressor(grads, state["comp"])
            p, o = self.opt.update(grads, state["opt"], state["params"],
                                   lr=lr)
            out["params"], out["opt"] = p, o
            # updated slow-tier leaves go back where they came from
            return like_placement(out, placed)

        self._micro_value_and_grad = micro_value_and_grad
        self._apply_update = apply_update

        self.eval_fn = None                # (state, step) -> metrics dict
        self._test_pos = None
        if holdout is not None:
            self.attach_holdout(holdout)

    # ---------------------------------------------------------------- state
    def init_state(self):
        return self._state0

    def apply_plan(self, state):
        """Place every state leaf onto its planned memory tier (used on
        fresh state, after re-layout, and on checkpoint restore — raw
        restored leaves otherwise land back in the fast tier).

        The ``TieredExecutor`` makes the demotion real on every
        backend: leaves go to their tier's JAX memory kind when the
        backend has one (TPU), and into the executor's host byte store
        otherwise — ``step_fn`` then streams them device-ward per step
        (``fetch``) and writes updates back (``commit``).

        Sharded runs place onto the MESH instead: large tables
        row-sharded (the per-device capacity relief), small leaves
        replicated.  Host-tier demotions are not applied there — a
        mesh NamedSharding and a host-memory-kind placement are
        mutually exclusive device_puts, and silently doing one after
        the other would just undo the first — so ``n_offloaded`` stays
        0 and the tier plan remains documented intent that drives the
        per-device microbatch derivation."""
        if self.shard is not None and self.shard.is_sharded:
            self.n_offloaded = 0
            return self.shard.shard_state(state)
        state, self.n_offloaded = self.executor.place(state)
        return state

    def step_context(self):
        """The ambient context one engine step runs under: dp/mesh
        sharding hints on a sharded run (``dist.hints``), nothing on a
        single-device run.  The fault-tolerant loop enters this around
        the steps it drives (``runtime.loop.run_training``)."""
        if self.shard is None:
            return contextlib.nullcontext()
        return sharding_hints(dp=self.shard.dp, mesh=self.shard.build_mesh())

    def _device_batch(self, users, pos, neg):
        """Host arrays -> device arrays, leading dim sharded over the
        mesh's data-parallel axes when the run is sharded."""
        u, p, n = jnp.asarray(users), jnp.asarray(pos), jnp.asarray(neg)
        if self.shard is not None and self.shard.is_sharded:
            u, p, n = self.shard.shard_batch(u, p, n)
        return u, p, n

    @property
    def sched(self) -> LargeBatchSchedule:
        return self.plan.sched

    def out_dim(self) -> int:
        """Final embedding width, per the model's own contract."""
        return self.spec.out_dim(self.cfg.embed_dim, self.cfg.n_layers)

    def lr_for_epoch(self, epoch: int) -> float:
        """LR scaled to the batch *actually run* this epoch — the
        schedule batch rounded up to a whole number of GLOBAL
        microbatches (all P shards' samples count toward the realized
        batch) — so the Goyal scaling rule tracks the realized batch
        size and a sharded run scales exactly like the single-device
        run with the same global batch."""
        actual = self.plan.microbatches_for_epoch(epoch) \
            * self.plan.global_microbatch
        return self.sched.scaled_lr(actual)

    def steps_per_epoch(self, epoch: int) -> int:
        spe_micro = self.loader.steps_per_epoch()
        return max(1, spe_micro // self.plan.microbatches_for_epoch(epoch))

    def steps_for_epochs(self, n_epochs: int) -> int:
        return sum(self.steps_per_epoch(e) for e in range(n_epochs))

    # ---------------------------------------------------------------- step
    def grads_for_batch(self, params, users, pos, neg):
        """Microbatched gradient accumulation over one target batch.

        Per-chunk mean-loss gradients are combined weighted by chunk
        size, so the result equals the full-batch gradient even when the
        batch is not a microbatch multiple (pinned by
        tests/test_pipeline.py).  Returns (mean_loss, grads).  A ragged
        final chunk costs one extra jit trace; loader-fed batches are
        always full microbatches.

        Sharded runs chunk at the GLOBAL microbatch (P x per-shard
        microbatch) and shard each chunk's rows over the mesh, so every
        device computes its per-shard slice and GSPMD all-reduces
        (psums) the gradients of the replicated-or-row-sharded params.
        """
        mu = self.plan.global_microbatch
        n = len(users)
        k = max(1, math.ceil(n / mu))
        loss_sum = None      # device scalar: no host sync inside the loop
        acc = None
        for c in range(k):
            sl = slice(c * mu, min((c + 1) * mu, n))
            w = (sl.stop - sl.start) / n
            loss, grads = self._micro_value_and_grad(
                params, self.g,
                *self._device_batch(users[sl], pos[sl], neg[sl]))
            wl = loss * w
            wg = jax.tree.map(lambda t: t * w, grads)
            loss_sum = wl if loss_sum is None else loss_sum + wl
            acc = wg if acc is None else jax.tree.map(jnp.add, acc, wg)
        return float(loss_sum), acc

    def _next_target_batch(self, k: int, step: int):
        """Drain k loader microbatches into one (u, i+, i-) target batch.
        Negatives are seeded per (run seed, step) so a resumed run draws
        the same samples as an uninterrupted one."""
        us, ps = [], []
        for _ in range(k):
            u, i = next(self.loader)
            us.append(u)
            ps.append(i)
        users = np.concatenate(us)
        pos = np.concatenate(ps)
        rng = np.random.default_rng((self.cfg.seed, step))
        neg = rng.integers(0, self.n_items, len(users)).astype(np.int32)
        return users, pos, neg

    def _micro_pos(self) -> int:
        """Loader position as a linear microbatch counter.  EdgeLoader
        rolls epochs lazily (state (e, spe) before the roll), and
        ``g = e*spe + s`` makes consumption exactly ``g += 1``."""
        st = self.loader.state
        return st.epoch * self.loader.steps_per_epoch() + st.step

    def current_epoch(self) -> int:
        """The epoch the NEXT microbatch will come from (post-roll), so
        the first step of an epoch uses that epoch's batch and LR."""
        return self._micro_pos() // self.loader.steps_per_epoch()

    def seek(self, step: int) -> None:
        """Position the loader as if ``step`` pipeline steps had already
        run, so a checkpoint-resumed loop continues mid-schedule (same
        epoch, same accumulation factor, same sample order).  Closed
        form over epoch segments (each step consumes k(epoch)
        microbatches), so a deep resume costs O(epochs), not O(steps)."""
        from repro.data.loader import LoaderState
        spe = self.loader.steps_per_epoch()
        g = 0
        done = 0
        while done < step:
            e = g // spe
            k = self.plan.microbatches_for_epoch(e)
            # steps until the next epoch boundary can change k (the step
            # crossing the boundary still uses this epoch's k)
            t = min(step - done, max(1, math.ceil(((e + 1) * spe - g) / k)))
            g += t * k
            done += t
        if g == 0:
            self.loader.state = LoaderState(0, 0)
        else:
            e = (g - 1) // spe
            self.loader.state = LoaderState(e, g - e * spe)
        self._next_step = step

    def step_fn(self, state, step: int):
        """(state, step) -> (state, loss): the loop-consumable step.
        The CALLER enters ``step_context()`` around it — the
        fault-tolerant loop does so for every step it drives
        (``run_training(step_context=...)``), and ``repro.api.Run.step``
        for direct single steps — so the sharded accumulation step sees
        the dp/mesh sharding hints exactly once."""
        if step != self._next_step:
            self.seek(step)
        epoch = self.current_epoch()
        k = self.plan.microbatches_for_epoch(epoch)
        users, pos, neg = self._next_target_batch(k, step)
        # slow-tier leaves stream device-ward once per step (the tables
        # don't change inside one accumulated batch) through the
        # executor's double buffer, and the updated bytes stream back
        # afterwards — identity when nothing is demoted off-device.
        state = self.executor.fetch(state)
        loss, grads = self.grads_for_batch(state["params"], users, pos, neg)
        lr = jnp.float32(self.lr_for_epoch(epoch))
        self._next_step = step + 1
        return self.executor.commit(self._apply_update(state, grads, lr)), loss

    def on_relayout(self, state):
        """Loop straggler escalation: re-run the planner over the current
        tensor set and re-place the state (paper §8.1 automation).  On a
        sharded run the re-plan stays per shard: per-device profiles
        against the per-device budget, and the re-placed state goes back
        onto the mesh (``apply_plan``'s shard step)."""
        cfg = self.cfg
        self.plan = build_train_plan(
            cfg.arch, self.spec, state["params"], state["opt"], self.g,
            cfg.n_layers, cfg.embed_dim, self.sched, self.plan.impl,
            hbm_budget=cfg.hbm_budget, microbatch=self.plan.microbatch,
            shard=self.shard, topology=self.topology,
            policy=cfg.memory_policy, pins=cfg.memory_pins,
            embed_store=cfg.embed_store)
        self.executor = TieredExecutor(self.plan.plan,
                                       embed_store=cfg.embed_store)
        return self.apply_plan(state)

    # ---------------------------------------------------------------- eval
    def embeddings(self, state):
        """Final (user, item) embeddings for evaluation."""
        return self._forward(state["params"], self.g)

    def attach_holdout(self, holdout: InteractionData) -> None:
        """Enable periodic held-out evaluation: sets ``eval_fn`` (which
        the fault-tolerant loop calls every ``LoopConfig.eval_every``
        steps, appending to the report's metric history).  Evaluation
        rides the streaming top-K path — train items masked via the CSR
        structure, never a dense U×I matrix."""
        from repro.data.synth import group_by_user
        self._test_pos = group_by_user(holdout.user, holdout.item,
                                       self.g.n_users)

        def eval_fn(state, step):
            return self.evaluate(state)

        self.eval_fn = eval_fn

    def eval_user_batch(self) -> int:
        """User microbatch for one eval sweep: configured, or derived
        from the HBM left after the training plan's placements."""
        if self.cfg.eval_user_batch is not None:
            return int(self.cfg.eval_user_batch)
        from repro.pipeline.plan import derive_eval_batch
        free = self.plan.hbm_budget - self.plan.plan.hbm_used
        return derive_eval_batch(free, self.out_dim(), self.cfg.eval_k,
                                 self.cfg.eval_item_block)

    def evaluate(self, state) -> dict:
        """One held-out eval sweep (recall/NDCG@eval_k + MRR) through
        ``repro.eval`` on the current state."""
        if self._test_pos is None:
            raise RuntimeError("no holdout attached; call attach_holdout")
        from repro.eval import evaluate_embeddings   # lazy: engine<->eval
        with self.step_context():
            ue, ie = self.embeddings(state)
        indptr, items = self.g.seen_csr()
        return evaluate_embeddings(
            ue, ie, self._test_pos, k=self.cfg.eval_k,
            seen_indptr=indptr, seen_items=items,
            user_batch=self.eval_user_batch(),
            item_block=self.cfg.eval_item_block, impl=self.plan.impl,
            shard=self.shard)


def build_pipeline(cfg: PipelineConfig, train: InteractionData,
                   holdout: InteractionData | None = None) -> Pipeline:
    return Pipeline(cfg, train, holdout=holdout)
