"""Cell builder: (architecture x input-shape x mesh) -> lowering-ready
step function + abstract input specs + shardings.

A "cell" is one entry of the dry-run/roofline matrix.  Everything here is
allocation-free: parameters and optimizer state are jax.eval_shape'd
ShapeDtypeStructs; the dry-run lowers with them directly.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs as config_registry
from repro.launch import shardings as shd
from repro.launch.mesh import all_axes, dp_axes, dp_size
from repro.optim import adafactor, adam

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _make_opt(name: str):
    return adam(1e-4) if name == "adam" else adafactor(1e-2)


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    fn: Callable                      # positional-args step function
    args: tuple                       # ShapeDtypeStructs
    in_shardings: tuple
    out_shardings: Any
    donate: tuple[int, ...]
    meta: dict


def list_cells(arch_id: str) -> list[str]:
    mod = config_registry.get(arch_id)
    return list(mod.SHAPES.keys())


def skipped_cells(arch_id: str) -> dict[str, str]:
    return dict(config_registry.get(arch_id).SKIP)


def build_cell(arch_id: str, shape_name: str, mesh) -> Cell:
    arch_id = config_registry.canon(arch_id)
    mod = config_registry.get(arch_id)
    if shape_name in mod.SKIP:
        raise ValueError(f"{arch_id}/{shape_name} skipped: {mod.SKIP[shape_name]}")
    shape = mod.SHAPES[shape_name]
    kind = shape["kind"]
    builder = _BUILDERS[kind]
    return builder(arch_id, mod, shape_name, shape, mesh)


# =========================================================== LM family

def _lm_param_struct(cfg):
    from repro.models.transformer import init_params
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))


def _lm_train(arch, mod, shape_name, shape, mesh):
    from repro.models import transformer as tfm
    cfg = mod.FULL
    opt = _make_opt(mod.OPTIMIZER)
    b, s = shape["global_batch"], shape["seq_len"]
    dp = dp_axes(mesh)
    # each microbatch must still shard its batch dim over dp
    mb = min(shape.get("microbatches", 1), b // dp_size(mesh))
    p_struct = _lm_param_struct(cfg)
    o_struct = jax.eval_shape(opt.init, p_struct)
    p_spec = shd.lm_param_specs(cfg, mesh)
    o_spec = shd.opt_state_specs(mod.OPTIMIZER, p_spec, p_struct)

    mb_c = lambda t: jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(dp, None)))
    lg_c = lambda t: jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(dp, None, "model")))
    # sequence-parallel residual stream (Megatron-SP): the remat carry
    # stack [L, B, S, D] shards S over 'model' as well — without this the
    # per-device stack is L*S_mb*D bytes (13.5 GiB bf16 on the 340B) and
    # XLA additionally hoists an f32 copy of it out of the backward loop
    act_c = lambda t: jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(dp, "model", None)))
    final_c = lambda t: jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(dp, None, None)))

    # bf16 grad accumulation for adafactor giants (340B/1T): the f32
    # accumulator alone is 4 TB on kimi-k2 (16 GiB/chip on a pod)
    gdt = jnp.bfloat16 if mod.OPTIMIZER == "adafactor" else jnp.float32
    grad_c = lambda g: jax.tree.map(
        lambda t, s: jax.lax.with_sharding_constraint(t, NamedSharding(mesh, s)),
        g, p_spec, is_leaf=lambda x: isinstance(x, jnp.ndarray))

    def step(params, opt_state, tokens, labels):
        from repro.dist.hints import sharding_hints
        with sharding_hints(dp=dp, tp="model"):
            return tfm.train_step(cfg, opt, params, opt_state, tokens, labels,
                                  n_microbatches=mb, mb_constraint=mb_c,
                                  logits_constraint=lg_c, act_constraint=act_c,
                                  grad_dtype=gdt, grad_constraint=grad_c,
                                  final_constraint=final_c)

    args = (p_struct, o_struct, _sds((b, s), I32), _sds((b, s), I32))
    in_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
             NamedSharding(mesh, P(dp, None)), NamedSharding(mesh, P(dp, None)))
    out_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
              NamedSharding(mesh, P()))
    n_par = cfg.param_count()
    n_act = cfg.active_param_count()
    return Cell(arch, shape_name, "train", step, args, in_sh, out_sh,
                donate=(0, 1),
                meta=dict(model_flops=6 * n_act * b * s, params=n_par,
                          active_params=n_act, tokens=b * s))


def _lm_prefill(arch, mod, shape_name, shape, mesh):
    from repro.models import transformer as tfm
    cfg = mod.FULL
    b, s = shape["global_batch"], shape["seq_len"]
    dp = dp_axes(mesh)
    p_struct = _lm_param_struct(cfg)
    p_spec = shd.lm_param_specs(cfg, mesh)
    cache_spec = shd.lm_cache_specs(cfg, mesh, b)

    def step(params, tokens):
        from repro.dist.hints import sharding_hints
        with sharding_hints(dp=dp, tp="model"):
            return tfm.prefill(cfg, params, tokens)

    args = (p_struct, _sds((b, s), I32))
    in_sh = (shd.named(mesh, p_spec), NamedSharding(mesh, P(dp, None)))
    out_sh = (NamedSharding(mesh, P(dp, "model")),
              shd.named(mesh, cache_spec))
    n_act = cfg.active_param_count()
    return Cell(arch, shape_name, "prefill", step, args, in_sh, out_sh,
                donate=(),
                meta=dict(model_flops=2 * n_act * b * s, params=cfg.param_count(),
                          active_params=n_act, tokens=b * s))


def _lm_decode(arch, mod, shape_name, shape, mesh):
    from repro.models import transformer as tfm
    cfg = mod.FULL
    b, s = shape["global_batch"], shape["seq_len"]
    dp = dp_axes(mesh)
    p_struct = _lm_param_struct(cfg)
    p_spec = shd.lm_param_specs(cfg, mesh)
    cache_spec = shd.lm_cache_specs(cfg, mesh, b)
    dt = jnp.dtype(cfg.dtype)
    cache_struct = {
        "k": _sds((cfg.n_layers, b, cfg.n_kv_heads, s, cfg.head_dim), dt),
        "v": _sds((cfg.n_layers, b, cfg.n_kv_heads, s, cfg.head_dim), dt),
    }
    tok_spec = P(dp, None) if b % dp_size(mesh) == 0 else P(None, None)
    logit_spec = P(dp, "model") if b % dp_size(mesh) == 0 else P(None, "model")

    def step(params, token, cache, pos):
        from repro.dist.hints import sharding_hints
        with sharding_hints(dp=dp, tp="model"):
            return tfm.decode_step(cfg, params, token, cache, pos)

    args = (p_struct, _sds((b, 1), I32), cache_struct, _sds((), I32))
    in_sh = (shd.named(mesh, p_spec), NamedSharding(mesh, tok_spec),
             shd.named(mesh, cache_spec), NamedSharding(mesh, P()))
    out_sh = (NamedSharding(mesh, logit_spec), shd.named(mesh, cache_spec))
    n_act = cfg.active_param_count()
    return Cell(arch, shape_name, "decode", step, args, in_sh, out_sh,
                donate=(2,),
                meta=dict(model_flops=2 * n_act * b, params=cfg.param_count(),
                          active_params=n_act, tokens=b, kv_len=s))


# =========================================================== GNN family

def _gcn_cfg_for_shape(mod, shape):
    from repro.models.gcn import GCNConfig
    base = mod.FULL
    return GCNConfig(name=base.name, n_layers=base.n_layers,
                     d_hidden=base.d_hidden, n_classes=shape["n_classes"],
                     d_feat=shape["d_feat"])


def _gcn_param_struct(cfg):
    from repro.models.gcn import init_params
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))


def _gnn_full(arch, mod, shape_name, shape, mesh):
    from repro.core.graph import Graph
    from repro.models import gcn
    cfg = _gcn_cfg_for_shape(mod, shape)
    opt = _make_opt(mod.OPTIMIZER)
    nd = mesh.devices.size
    n_pad = _round_up(shape["n_nodes"], nd)
    e_pad = _round_up(shape["n_edges"], nd)
    ax = all_axes(mesh)

    p_struct = _gcn_param_struct(cfg)
    o_struct = jax.eval_shape(opt.init, p_struct)
    p_spec = shd.gcn_param_specs(cfg, mesh)
    o_spec = shd.opt_state_specs(mod.OPTIMIZER, p_spec, p_struct)

    def step(params, opt_state, x, src, dst, emask, labels, lmask):
        g = Graph(src, dst, emask, n_pad, shape["n_edges"])
        loss, grads = jax.value_and_grad(
            lambda p: gcn.loss_fn(cfg, p, g, x, labels, lmask))(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    args = (p_struct, o_struct,
            _sds((n_pad, shape["d_feat"]), F32),
            _sds((e_pad,), I32), _sds((e_pad,), I32), _sds((e_pad,), jnp.bool_),
            _sds((n_pad,), I32), _sds((n_pad,), F32))
    in_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
             NamedSharding(mesh, P(ax, None)),
             NamedSharding(mesh, P(ax)), NamedSharding(mesh, P(ax)),
             NamedSharding(mesh, P(ax)),
             NamedSharding(mesh, P(ax)), NamedSharding(mesh, P(ax)))
    out_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
              NamedSharding(mesh, P()))
    dims = [shape["d_feat"]] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    spmm_flops = sum(2 * shape["n_edges"] * d for d in dims[:-1])
    mm_flops = sum(2 * shape["n_nodes"] * dims[i] * dims[i + 1]
                   for i in range(len(dims) - 1))
    return Cell(arch, shape_name, "gnn_full", step, args, in_sh, out_sh,
                donate=(0, 1),
                meta=dict(model_flops=3 * (spmm_flops + mm_flops),
                          n_nodes=shape["n_nodes"], n_edges=shape["n_edges"]))


def _gnn_sampled(arch, mod, shape_name, shape, mesh):
    from repro.models import gcn
    cfg = _gcn_cfg_for_shape(mod, shape)
    opt = _make_opt(mod.OPTIMIZER)
    nd = mesh.devices.size
    ax = all_axes(mesh)
    seeds = shape["batch_nodes"]
    f1, f2 = shape["fanouts"]
    # static block sizes (upper bounds, mesh-divisible)
    n1_dst = seeds
    e1 = _round_up(seeds * f1, nd)
    n1_src = _round_up(seeds * (f1 + 1), nd)
    e2 = _round_up(n1_src * f2, nd)
    n2_src = _round_up(n1_src * (f2 + 1), nd)

    p_struct = _gcn_param_struct(cfg)
    o_struct = jax.eval_shape(opt.init, p_struct)
    p_spec = shd.gcn_param_specs(cfg, mesh)
    o_spec = shd.opt_state_specs(mod.OPTIMIZER, p_spec, p_struct)

    def step(params, opt_state, x, e2s, e2d, m2, e1s, e1d, m1, labels):
        blocks = [
            dict(edge_src=e2s, edge_dst=e2d, edge_mask=m2, n_dst=n1_src),
            dict(edge_src=e1s, edge_dst=e1d, edge_mask=m1, n_dst=n1_dst),
        ]

        def loss_fn(p):
            logits = gcn.forward_blocks(cfg, p, blocks, x)
            logp = jax.nn.log_softmax(logits, -1)
            ll = jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]
            return -jnp.mean(ll)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    args = (p_struct, o_struct,
            _sds((n2_src, shape["d_feat"]), F32),
            _sds((e2,), I32), _sds((e2,), I32), _sds((e2,), jnp.bool_),
            _sds((e1,), I32), _sds((e1,), I32), _sds((e1,), jnp.bool_),
            _sds((seeds,), I32))
    in_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
             NamedSharding(mesh, P(ax, None)),
             NamedSharding(mesh, P(ax)), NamedSharding(mesh, P(ax)),
             NamedSharding(mesh, P(ax)),
             NamedSharding(mesh, P(ax)), NamedSharding(mesh, P(ax)),
             NamedSharding(mesh, P(ax)),
             NamedSharding(mesh, P(ax)))
    out_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
              NamedSharding(mesh, P()))
    flops = 2 * e2 * shape["d_feat"] + 2 * n1_src * shape["d_feat"] * cfg.d_hidden \
        + 2 * e1 * cfg.d_hidden + 2 * seeds * cfg.d_hidden * shape["n_classes"]
    return Cell(arch, shape_name, "gnn_sampled", step, args, in_sh, out_sh,
                donate=(0, 1), meta=dict(model_flops=3 * flops,
                                         sampled_src=n2_src, sampled_edges=e2))


def _gnn_batched(arch, mod, shape_name, shape, mesh):
    from repro.models import gcn
    cfg = _gcn_cfg_for_shape(mod, shape)
    opt = _make_opt(mod.OPTIMIZER)
    nd = mesh.devices.size
    ax = all_axes(mesh)
    bsz = shape["batch"]
    n_flat = _round_up(bsz * shape["n_nodes"], nd)
    e_flat = _round_up(bsz * shape["n_edges"], nd)

    p_struct = _gcn_param_struct(cfg)
    o_struct = jax.eval_shape(opt.init, p_struct)
    p_spec = shd.gcn_param_specs(cfg, mesh)
    o_spec = shd.opt_state_specs(mod.OPTIMIZER, p_spec, p_struct)

    def step(params, opt_state, x, src, dst, emask, gids, labels):
        def loss_fn(p):
            logits = gcn.forward_batched(cfg, p, src, dst, emask, x, gids, bsz)
            logp = jax.nn.log_softmax(logits, -1)
            ll = jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]
            return -jnp.mean(ll)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    args = (p_struct, o_struct,
            _sds((n_flat, shape["d_feat"]), F32),
            _sds((e_flat,), I32), _sds((e_flat,), I32), _sds((e_flat,), jnp.bool_),
            _sds((n_flat,), I32), _sds((bsz,), I32))
    in_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
             NamedSharding(mesh, P(ax, None)),
             NamedSharding(mesh, P(ax)), NamedSharding(mesh, P(ax)),
             NamedSharding(mesh, P(ax)),
             NamedSharding(mesh, P(ax)), NamedSharding(mesh, P()))
    out_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
              NamedSharding(mesh, P()))
    return Cell(arch, shape_name, "gnn_batched", step, args, in_sh, out_sh,
                donate=(0, 1),
                meta=dict(model_flops=3 * 2 * e_flat * shape["d_feat"]))


# =========================================================== recsys family

def _recsys_init_struct(arch, cfg):
    from repro.models import recsys_models as rm
    init = {"deepfm": rm.deepfm_init, "xdeepfm": rm.xdeepfm_init,
            "dlrm_rm2": rm.dlrm_init, "bert4rec": rm.bert4rec_init}[arch]
    return jax.eval_shape(lambda k: init(cfg, k), jax.random.PRNGKey(0))


def _recsys_forward(arch, cfg):
    from repro.models import recsys_models as rm
    return {"deepfm": partial(rm.deepfm_forward, cfg),
            "xdeepfm": partial(rm.xdeepfm_forward, cfg),
            "dlrm_rm2": partial(rm.dlrm_forward, cfg)}[arch]


def _recsys_embedding_flops(arch, cfg, batch):
    # lookups dominate bytes, interaction+MLP dominates FLOPs
    if arch == "dlrm_rm2":
        f = cfg.n_sparse + 1
        mlp = sum(a * b for a, b in zip((cfg.n_dense,) + cfg.bot_mlp[:-1],
                                        cfg.bot_mlp))
        top_in = cfg.bot_mlp[-1] + f * (f - 1) // 2
        mlp += sum(a * b for a, b in zip((top_in,) + cfg.top_mlp[:-1],
                                         cfg.top_mlp))
        inter = f * f * cfg.embed_dim
        return 2 * batch * (mlp + inter)
    d_in = cfg.n_sparse * cfg.embed_dim
    mlp = sum(a * b for a, b in zip((d_in,) + cfg.mlp_dims[:-1],
                                    cfg.mlp_dims)) + cfg.mlp_dims[-1]
    extra = 0
    if hasattr(cfg, "cin_layers"):
        h_prev = cfg.n_sparse
        for h in cfg.cin_layers:
            extra += h * h_prev * cfg.n_sparse * cfg.embed_dim
            h_prev = h
    else:
        extra = cfg.n_sparse * cfg.embed_dim  # FM
    return 2 * batch * (mlp + extra)


def _recsys_io(arch, cfg, batch, mesh, with_labels):
    """(arg structs, shardings) for dense/ids(/labels) inputs."""
    dpall = all_axes(mesh)
    nd = mesh.devices.size
    bspec = P(dpall) if batch % nd == 0 else P()
    bspec2 = P(dpall, None) if batch % nd == 0 else P(None, None)
    args, shs = [], []
    if arch == "dlrm_rm2":
        args.append(_sds((batch, cfg.n_dense), F32))
        shs.append(NamedSharding(mesh, bspec2))
    args.append(_sds((batch, cfg.n_sparse), I32))
    shs.append(NamedSharding(mesh, bspec2))
    if with_labels:
        args.append(_sds((batch,), F32))
        shs.append(NamedSharding(mesh, bspec))
    return args, shs, bspec


def _recsys_train_rowwise(arch, mod, shape_name, shape, mesh):
    """dlrm-rm2 variant: lazy row-wise AdaGrad on the embedding tables
    (REPRO_RECSYS_OPT=rowwise).  Dense towers keep Adam; tables touch only
    the B*F gathered rows per step instead of the full [F, V, D] tensor
    (+m,v) that dense Adam streams."""
    from repro.models.recsys_models import (bce_loss, dlrm_forward_from_emb,
                                            lookup_fields,
                                            rowwise_adagrad_update)
    cfg = mod.FULL
    opt = _make_opt(mod.OPTIMIZER)
    batch = shape["batch"]
    dpall = all_axes(mesh)
    p_struct = _recsys_init_struct(arch, cfg)
    p_spec = shd.recsys_param_specs(arch, p_struct, mesh)
    dense_keys = ("bot", "top")
    dense_struct = {k: p_struct[k] for k in dense_keys}
    o_struct = {
        "acc": _sds((cfg.n_sparse, cfg.vocab), F32),
        "mlp": jax.eval_shape(opt.init, dense_struct),
    }
    o_spec = {
        "acc": P(None, dpall),
        "mlp": shd.opt_state_specs(mod.OPTIMIZER,
                                   {k: p_spec[k] for k in dense_keys},
                                   dense_struct),
    }
    data_args, data_sh, _ = _recsys_io(arch, cfg, batch, mesh, with_labels=True)

    def step(params, opt_state, dense, ids, labels):
        emb = lookup_fields(params["tables"], ids)

        def loss_fn(emb, mlps):
            p2 = dict(params, **mlps)
            return bce_loss(dlrm_forward_from_emb(cfg, p2, dense, emb), labels)

        mlps = {k: params[k] for k in dense_keys}
        loss, (g_emb, g_mlp) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            emb, mlps)
        tables, acc = rowwise_adagrad_update(params["tables"],
                                             opt_state["acc"], ids, g_emb)
        new_mlps, mlp_state = opt.update(g_mlp, opt_state["mlp"], mlps)
        new_params = dict(params, tables=tables, **new_mlps)
        return new_params, {"acc": acc, "mlp": mlp_state}, loss

    args = (p_struct, o_struct, *data_args)
    in_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec), *data_sh)
    out_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
              NamedSharding(mesh, P()))
    d = cfg.embed_dim
    touched = batch * cfg.n_sparse
    analytic_hbm = (6 * touched * d * 4          # gather + scatter + scale rows
                    + 4 * touched * 4            # accumulator rows
                    + 3 * 8 * batch * 1024 * 4)  # mlp fwd/bwd approx
    analytic_coll = 6 * touched * d * 4          # a2a-ish lookup + grad return
    return Cell(arch, shape_name, "recsys_train", step, args, in_sh, out_sh,
                donate=(0, 1),
                meta=dict(model_flops=3 * _recsys_embedding_flops(arch, cfg, batch),
                          batch=batch, analytic_hbm=float(analytic_hbm),
                          analytic_coll=float(analytic_coll),
                          variant="rowwise_adagrad"))


def _recsys_train(arch, mod, shape_name, shape, mesh):
    import os
    if os.environ.get("REPRO_RECSYS_OPT") == "rowwise" and arch == "dlrm_rm2":
        return _recsys_train_rowwise(arch, mod, shape_name, shape, mesh)
    from repro.models.recsys_models import bce_loss
    cfg = mod.FULL
    opt = _make_opt(mod.OPTIMIZER)
    batch = shape["batch"]
    fwd = _recsys_forward(arch, cfg)
    p_struct = _recsys_init_struct(arch, cfg)
    o_struct = jax.eval_shape(opt.init, p_struct)
    p_spec = shd.recsys_param_specs(arch, p_struct, mesh)
    o_spec = shd.opt_state_specs(mod.OPTIMIZER, p_spec, p_struct)

    data_args, data_sh, _ = _recsys_io(arch, cfg, batch, mesh, with_labels=True)

    def step(params, opt_state, *data):
        *feats, labels = data

        def loss_fn(p):
            return bce_loss(fwd(p, *feats), labels)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    args = (p_struct, o_struct, *data_args)
    in_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec), *data_sh)
    out_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
              NamedSharding(mesh, P()))
    d = cfg.embed_dim
    table_bytes = cfg.n_sparse * cfg.vocab * d * 4
    touched = batch * cfg.n_sparse
    # dense Adam streams the whole table + m + v (read+write each)
    analytic_hbm = 6 * table_bytes + 4 * touched * d * 4
    analytic_coll = 6 * touched * d * 4
    return Cell(arch, shape_name, "recsys_train", step, args, in_sh, out_sh,
                donate=(0, 1),
                meta=dict(model_flops=3 * _recsys_embedding_flops(arch, cfg, batch),
                          batch=batch, analytic_hbm=float(analytic_hbm),
                          analytic_coll=float(analytic_coll),
                          variant="dense_adam"))


def _recsys_serve(arch, mod, shape_name, shape, mesh):
    cfg = mod.FULL
    batch = shape["batch"]
    fwd = _recsys_forward(arch, cfg)
    p_struct = _recsys_init_struct(arch, cfg)
    p_spec = shd.recsys_param_specs(arch, p_struct, mesh)
    data_args, data_sh, bspec = _recsys_io(arch, cfg, batch, mesh,
                                           with_labels=False)

    def step(params, *feats):
        return fwd(params, *feats)

    args = (p_struct, *data_args)
    in_sh = (shd.named(mesh, p_spec), *data_sh)
    out_sh = NamedSharding(mesh, bspec)
    return Cell(arch, shape_name, "recsys_serve", step, args, in_sh, out_sh,
                donate=(),
                meta=dict(model_flops=_recsys_embedding_flops(arch, cfg, batch),
                          batch=batch))


def _recsys_retrieval(arch, mod, shape_name, shape, mesh):
    from repro.models.recsys_models import dlrm_retrieve
    cfg = mod.FULL
    c = shape["n_candidates"]
    dpall = all_axes(mesh)
    p_struct = _recsys_init_struct(arch, cfg)
    p_spec = shd.recsys_param_specs(arch, p_struct, mesh)

    if arch == "dlrm_rm2":
        def step(params, dense, ids, cand):
            return dlrm_retrieve(cfg, params, dense, ids, cand)
        args = (p_struct, _sds((1, cfg.n_dense), F32),
                _sds((1, cfg.n_sparse), I32), _sds((c,), I32))
        in_sh = (shd.named(mesh, p_spec), NamedSharding(mesh, P(None, None)),
                 NamedSharding(mesh, P(None, None)),
                 NamedSharding(mesh, P(dpall)))
    else:
        fwd = _recsys_forward(arch, cfg)

        def step(params, ids, cand):
            # broadcast user fields, swap field 0 with the candidates
            ids_b = jnp.broadcast_to(ids, (c, cfg.n_sparse))
            ids_b = ids_b.at[:, 0].set(cand)
            return fwd(params, ids_b)
        args = (p_struct, _sds((1, cfg.n_sparse), I32), _sds((c,), I32))
        in_sh = (shd.named(mesh, p_spec), NamedSharding(mesh, P(None, None)),
                 NamedSharding(mesh, P(dpall)))
    out_sh = NamedSharding(mesh, P(dpall))
    return Cell(arch, shape_name, "recsys_retrieval", step, args, in_sh, out_sh,
                donate=(),
                meta=dict(model_flops=_recsys_embedding_flops(arch, cfg, c),
                          candidates=c))


# =========================================================== bert4rec (seq)

def _seq_train(arch, mod, shape_name, shape, mesh):
    from repro.models.recsys_models import bert4rec_sampled_loss
    cfg = mod.FULL
    opt = _make_opt(mod.OPTIMIZER)
    b = shape["batch"]
    m, n_neg = mod.N_MASKED, mod.N_NEGATIVES
    dpall = all_axes(mesh)
    p_struct = _recsys_init_struct(arch, cfg)
    o_struct = jax.eval_shape(opt.init, p_struct)
    p_spec = shd.recsys_param_specs(arch, p_struct, mesh)
    o_spec = shd.opt_state_specs(mod.OPTIMIZER, p_spec, p_struct)

    def step(params, opt_state, seq, smask, mpos, labels, negs):
        def loss_fn(p):
            return bert4rec_sampled_loss(cfg, p, seq, smask, mpos, labels, negs)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    s = cfg.seq_len
    args = (p_struct, o_struct, _sds((b, s), I32), _sds((b, s), jnp.bool_),
            _sds((b, m), I32), _sds((b, m), I32), _sds((b, m, n_neg), I32))
    dsh = lambda *sp: NamedSharding(mesh, P(*sp))
    in_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
             dsh(dpall, None), dsh(dpall, None), dsh(dpall, None),
             dsh(dpall, None), dsh(dpall, None, None))
    out_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
              NamedSharding(mesh, P()))
    d = cfg.embed_dim
    enc = cfg.n_blocks * (4 * d * d + 2 * d * cfg.d_ff) * s * b * 2 \
        + cfg.n_blocks * 2 * b * s * s * d * 2
    return Cell(arch, shape_name, "seq_train", step, args, in_sh, out_sh,
                donate=(0, 1),
                meta=dict(model_flops=3 * (enc + 2 * b * m * (n_neg + 1) * d),
                          batch=b))


def _seq_serve(arch, mod, shape_name, shape, mesh):
    from repro.models.recsys_models import bert4rec_serve
    cfg = mod.FULL
    b = shape["batch"]
    slate = 1024
    dpall = all_axes(mesh)
    nd = mesh.devices.size
    p_struct = _recsys_init_struct(arch, cfg)
    p_spec = shd.recsys_param_specs(arch, p_struct, mesh)
    bspec = dpall if b % nd == 0 else None

    def step(params, seq, smask, slate_ids):
        return bert4rec_serve(cfg, params, seq, smask, slate_ids)

    s = cfg.seq_len
    args = (p_struct, _sds((b, s), I32), _sds((b, s), jnp.bool_),
            _sds((b, slate), I32))
    in_sh = (shd.named(mesh, p_spec), NamedSharding(mesh, P(bspec, None)),
             NamedSharding(mesh, P(bspec, None)),
             NamedSharding(mesh, P(bspec, None)))
    out_sh = (NamedSharding(mesh, P(bspec, None)),
              NamedSharding(mesh, P(bspec, None)))
    d = cfg.embed_dim
    enc = cfg.n_blocks * (4 * d * d + 2 * d * cfg.d_ff) * s * b * 2 \
        + cfg.n_blocks * 2 * b * s * s * d * 2
    return Cell(arch, shape_name, "seq_serve", step, args, in_sh, out_sh,
                donate=(), meta=dict(model_flops=enc, batch=b))


def _seq_retrieval(arch, mod, shape_name, shape, mesh):
    from repro.models.recsys_models import bert4rec_retrieve
    cfg = mod.FULL
    b, c = shape["batch"], shape["n_candidates"]
    dpall = all_axes(mesh)
    p_struct = _recsys_init_struct(arch, cfg)
    p_spec = shd.recsys_param_specs(arch, p_struct, mesh)

    def step(params, seq, smask, cand):
        return bert4rec_retrieve(cfg, params, seq, smask, cand)

    s = cfg.seq_len
    args = (p_struct, _sds((b, s), I32), _sds((b, s), jnp.bool_),
            _sds((c,), I32))
    in_sh = (shd.named(mesh, p_spec), NamedSharding(mesh, P(None, None)),
             NamedSharding(mesh, P(None, None)), NamedSharding(mesh, P(dpall)))
    out_sh = NamedSharding(mesh, P(None, dpall))
    return Cell(arch, shape_name, "seq_retrieval", step, args, in_sh, out_sh,
                donate=(),
                meta=dict(model_flops=2 * b * c * cfg.embed_dim, candidates=c))


# =========================================================== NGCF/LightGCN

def _gnnrecsys_train(arch, mod, shape_name, shape, mesh):
    from repro.core import bpr, lightgcn, ngcf
    from repro.core.graph import BipartiteGraph
    cfg = mod.FULL
    opt = _make_opt(mod.OPTIMIZER)
    dpall = all_axes(mesh)
    nd = mesh.devices.size
    e_pad = _round_up(cfg.n_edges, nd)
    is_ngcf = arch == "ngcf"

    if is_ngcf:
        p_struct = jax.eval_shape(
            lambda k: ngcf.init_params(k, cfg.n_users, cfg.n_items,
                                       cfg.embed_dim, cfg.n_layers),
            jax.random.PRNGKey(0))
    else:
        p_struct = jax.eval_shape(
            lambda k: lightgcn.init_params(k, cfg.n_users, cfg.n_items,
                                           cfg.embed_dim),
            jax.random.PRNGKey(0))
    o_struct = jax.eval_shape(opt.init, p_struct)
    p_spec = shd.gnnrecsys_param_specs(cfg, mesh, "ngcf" if is_ngcf else "lightgcn")
    o_spec = shd.opt_state_specs(mod.OPTIMIZER, p_spec, p_struct)

    def step(params, opt_state, user, item, emask, bu, bi, bn):
        g = BipartiteGraph(user, item, emask, cfg.n_users, cfg.n_items,
                           cfg.n_edges)

        def loss_fn(p):
            if is_ngcf:
                ue, ie = ngcf.forward(p, g, opt_level=3)
            else:
                ue, ie = lightgcn.forward(p, g, n_layers=cfg.n_layers)
            return bpr.bpr_loss(ue, ie, bu, bi, bn)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    bb = cfg.bpr_batch
    args = (p_struct, o_struct,
            _sds((e_pad,), I32), _sds((e_pad,), I32), _sds((e_pad,), jnp.bool_),
            _sds((bb,), I32), _sds((bb,), I32), _sds((bb,), I32))
    esh = NamedSharding(mesh, P(dpall))
    in_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
             esh, esh, esh, esh, esh, esh)
    out_sh = (shd.named(mesh, p_spec), shd.named(mesh, o_spec),
              NamedSharding(mesh, P()))
    d = cfg.embed_dim
    # per layer: SDDMM mul (E*D) + 2 SpMM (E*D each); NGCF adds O(V*D^2) matmuls
    per_layer = 3 * 2 * cfg.n_edges * d
    if is_ngcf:
        per_layer += 2 * (cfg.n_users + cfg.n_items) * d * d * 2
    flops = 3 * cfg.n_layers * per_layer
    meta = dict(model_flops=flops, n_edges=cfg.n_edges, bpr_batch=bb)
    if is_ngcf:
        # NGCF byte model (paper §2.1): the dominant HBM term is the
        # per-layer [E, D] Hadamard message stream — written by the
        # SDDMM-mul, read by both SpMMs, and saved/re-read as an
        # autodiff residual (~4 touches/layer, both directions).  The
        # fused hadamard_spmm route forms the product in VMEM and
        # rematerializes it in backward, so that term vanishes; the
        # node-level gather/scatter and optimizer traffic stand.
        row = d * 4
        v = cfg.n_users + cfg.n_items
        msg_bytes = cfg.n_layers * 2 * 4 * cfg.n_edges * row
        node_bytes = cfg.n_layers * 3 * 2 * (2 * cfg.n_edges + 2 * v) * row
        opt_bytes = 6 * (cfg.n_layers + 1) * v * row   # adam: p+m+v r/w
        coll_bytes = 2 * (cfg.n_layers + 1) * v * row  # grad all-reduce
        meta.update(analytic_hbm=float(msg_bytes + node_bytes + opt_bytes),
                    analytic_coll=float(coll_bytes),
                    hadamard_msg_hbm_bytes=float(msg_bytes))
    return Cell(arch, shape_name, "gnnrecsys_train", step, args, in_sh, out_sh,
                donate=(0, 1), meta=meta)


_BUILDERS = {
    "train": _lm_train,
    "prefill": _lm_prefill,
    "decode": _lm_decode,
    "gnn_full": _gnn_full,
    "gnn_sampled": _gnn_sampled,
    "gnn_batched": _gnn_batched,
    "recsys_train": _recsys_train,
    "recsys_serve": _recsys_serve,
    "recsys_retrieval": _recsys_retrieval,
    "seq_train": _seq_train,
    "seq_serve": _seq_serve,
    "seq_retrieval": _seq_retrieval,
    "gnnrecsys_train": _gnnrecsys_train,
}


def input_specs(arch_id: str, shape_name: str, mesh):
    """Paper-required entry point: ShapeDtypeStruct stand-ins for every
    model input of the given cell."""
    return build_cell(arch_id, shape_name, mesh).args
