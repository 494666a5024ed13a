"""Jittable step builders for every (arch × shape) combination.

* ``build_train_step``   — Mode-B DynaBRO robust training step: FSDP params,
  partial-manual shard_map (manual over worker axes, auto over 'model'),
  robust-aggregating custom-VJP gathers, simulated Byzantine mask input.
  MLMC level j is expressed as a 2^j× larger per-worker batch (a mini-batch
  gradient of 2^j unit batches IS the level-j gradient — see DESIGN.md §3),
  so the aggregation applies to worker *means* exactly as in Algorithm 2.
* ``build_prefill_step`` — inference prefill (logits + cache).
* ``build_decode_step``  — one token against a seq_len KV cache.

Each returns (jitted_fn, example_inputs) where example_inputs are
ShapeDtypeStructs with NamedShardings — ready for ``.lower().compile()``.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.sharded import ShardedByzConfig, make_param_hook
from repro.launch import sharding as shl
from repro.launch.mesh import n_workers, worker_axes, worker_iota, worker_spec
from repro.models import loss_fn, decode_step, prefill
from repro.optim.optimizers import Optimizer, apply_updates, sgd


# spec/SDS helpers live in launch.sharding now (shared with the scan driver)
_sds = shl.sds


@dataclasses.dataclass
class BuiltStep:
    fn: Any  # jitted callable
    inputs: Tuple  # ShapeDtypeStruct pytrees (positional)
    name: str


def _perf_cfg(cfg: ModelConfig, mesh: Mesh) -> ModelConfig:
    """Per-mesh perf knobs (§Perf). Env overrides allow A/B dry-runs:
    REPRO_ATTN_IMPL=chunked REPRO_MOE_GROUP=0 reproduces the baseline."""
    ms = mesh.shape["model"]
    impl = os.environ.get("REPRO_ATTN_IMPL", cfg.attn_impl)
    seq_shard = ""
    if impl == "flash" and not (cfg.n_heads % ms == 0 and cfg.n_kv_heads % ms == 0):
        # heads don't divide the model axis: shard the q-sequence dim instead
        seq_shard = os.environ.get("REPRO_ATTN_SEQ_SHARD", "model")
    tg = int(os.environ.get("REPRO_MOE_GROUP", str(cfg.moe_token_group)))
    es = ""
    if cfg.is_moe and cfg.n_experts % ms == 0 and impl == "flash":
        es = os.environ.get("REPRO_MOE_EXPERT_SHARD", "model")
    return dataclasses.replace(cfg, attn_impl=impl, attn_seq_shard=seq_shard,
                               moe_token_group=tg, moe_expert_shard=es)


# ================================================================ train


@dataclasses.dataclass
class _TrainPlumbing:
    """Everything the two Mode-B train-step builders share — ONE spec / jit /
    ShapeDtypeStruct pipeline (DESIGN.md §9), so the plain-DynaBRO and MLMC
    steps cannot drift again (the old duplicated ~60 lines dropped the
    audio/vlm ``extra`` batch leaves from the MLMC path)."""
    cfg: ModelConfig
    byz: ShardedByzConfig
    specs: Any
    plans: dict
    opt: Optimizer
    ospecs: Any
    opt_state_shapes: Any
    batch_spec: Any
    batch_ex: Any
    waxes: Tuple[str, ...]
    m: int
    dtype: Any


def _train_plumbing(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig, *,
                    level_units: int, aggregator: str, attack: str,
                    delta: float, opt: Optional[Optimizer], lr: float,
                    agg_backend: str, dtype) -> _TrainPlumbing:
    cfg = _perf_cfg(cfg, mesh)
    waxes = worker_axes(mesh)
    m = n_workers(mesh)
    B = shape.global_batch * level_units
    if B % m:
        raise ValueError(
            f"global batch {B} not divisible by m={m} workers — Mode B "
            f"shards the batch over the worker axes")
    byz = ShardedByzConfig(axis_names=waxes, m=m, aggregator=aggregator,
                           delta=delta, attack=attack, backend=agg_backend)
    specs, plans = shl.plan_params(cfg, mesh, fsdp=True, dtype=dtype)
    opt = opt or sgd(lr)
    batch_spec, batch_ex = shl.batch_sds(cfg, mesh, B, shape.seq_len,
                                         kind="train", dtype=dtype)
    opt_state_shapes = jax.eval_shape(
        lambda: opt.init(shl.abstract_params(cfg, dtype)))
    ospecs = shl.opt_specs(opt_state_shapes, specs)
    return _TrainPlumbing(cfg, byz, specs, plans, opt, ospecs,
                          opt_state_shapes, batch_spec, batch_ex, waxes, m,
                          dtype)


def _wrap_train_step(pl: _TrainPlumbing, step_local, mesh: Mesh, aux_spec,
                     name: str) -> BuiltStep:
    """shard_map + jit + example-input assembly shared by both builders."""
    pspecs_manual = shl.strip_model(pl.specs)
    ospecs_manual = shl.strip_model(pl.ospecs)
    smapped = jax.shard_map(
        step_local, mesh=mesh,
        in_specs=(pspecs_manual, ospecs_manual, pl.batch_spec, P(None),
                  P(worker_spec(pl.waxes))),
        out_specs=(pspecs_manual, ospecs_manual, aux_spec),
        axis_names=set(pl.waxes), check_vma=False)

    def stepped(params, opt_state, batch, maskf):
        # worker-index iota: sharding over the worker axes hands each device
        # its own flattened index as data (see core.sharded.make_param_hook)
        return smapped(params, opt_state, batch, maskf, worker_iota(pl.m))

    jitted = jax.jit(
        stepped,
        in_shardings=(shl.named(mesh, pl.specs), shl.named(mesh, pl.ospecs),
                      shl.named(mesh, pl.batch_spec),
                      NamedSharding(mesh, P(None))),
        out_shardings=(shl.named(mesh, pl.specs), shl.named(mesh, pl.ospecs),
                       None),
        donate_argnums=(0, 1))
    params_in = shl.sds_tree(shl.abstract_params(pl.cfg, pl.dtype), pl.specs,
                             mesh)
    opt_in = shl.sds_tree(pl.opt_state_shapes, pl.ospecs, mesh)
    maskf = shl.sds((pl.m,), jnp.float32, mesh, P(None))
    return BuiltStep(jitted, (params_in, opt_in, pl.batch_ex, maskf), name)


def build_train_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig,
                     *, aggregator: str = "cwmed", attack: str = "none",
                     level: int = 0, lr: float = 1e-3, delta: float = 0.25,
                     opt: Optional[Optimizer] = None, agg_backend: str = "auto",
                     dtype=jnp.bfloat16) -> BuiltStep:
    pl = _train_plumbing(cfg, mesh, shape, level_units=2 ** level,
                         aggregator=aggregator, attack=attack, delta=delta,
                         opt=opt, lr=lr, agg_backend=agg_backend, dtype=dtype)
    cfg = pl.cfg

    def step_local(params, opt_state, batch, maskf, widx):
        hook = make_param_hook(pl.byz, pl.plans, maskf, widx)
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, param_hook=hook))(params)
        updates, opt_state = pl.opt.update(g, opt_state, params)
        params = apply_updates(params, updates)
        loss = jax.lax.pmean(loss, pl.waxes)
        return params, opt_state, loss

    return _wrap_train_step(pl, step_local, mesh, P(),
                            f"train[{cfg.arch_id}/{shape.name}/l{level}]")


# ================================================================ inference


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig,
                       dtype=jnp.bfloat16) -> BuiltStep:
    cfg = _perf_cfg(cfg, mesh)
    if shape.global_batch % mesh.shape["data"] == 0:
        cfg = dataclasses.replace(cfg, attn_batch_shard="data")
    specs, _ = shl.plan_params(cfg, mesh, fsdp=_infer_fsdp(cfg, mesh), dtype=dtype)
    B, S = shape.global_batch, shape.seq_len
    bspec = shl.batch_specs(cfg, mesh, B, "prefill")

    def fn(params, tokens, extra):
        return prefill(params, tokens, cfg, extra=extra)

    jitted = jax.jit(fn, in_shardings=(shl.named(mesh, specs),
                                       NamedSharding(mesh, bspec["tokens"]),
                                       shl.named(mesh, bspec.get("extra", {}))),
                     out_shardings=None)
    params_in = shl.sds_tree(shl.abstract_params(cfg, dtype), specs, mesh)
    tokens = _sds((B, S), jnp.int32, mesh, bspec["tokens"])
    extra = {}
    if cfg.family == "audio":
        extra = {"frames": _sds((B, cfg.encoder_seq, cfg.d_model), dtype, mesh,
                                bspec["extra"]["frames"])}
    if cfg.family == "vlm":
        extra = {"patches": _sds((B, cfg.n_image_tokens, cfg.d_model), dtype, mesh,
                                 bspec["extra"]["patches"])}
    return BuiltStep(jitted, (params_in, tokens, extra),
                     f"prefill[{cfg.arch_id}/{shape.name}]")


def build_decode_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig,
                      dtype=jnp.bfloat16) -> BuiltStep:
    cfg = _perf_cfg(cfg.for_shape(shape), mesh)
    specs, _ = shl.plan_params(cfg, mesh, fsdp=_infer_fsdp(cfg, mesh), dtype=dtype)
    B, S = shape.global_batch, shape.seq_len
    cache_shapes, cache_specs = shl.cache_spec_tree(cfg, mesh, B, S)
    tok_spec = P("data") if B % mesh.shape["data"] == 0 else P(None)

    def fn(params, cache, token, pos):
        return decode_step(params, cache, token, pos, cfg)

    jitted = jax.jit(fn, in_shardings=(shl.named(mesh, specs),
                                       shl.named(mesh, cache_specs),
                                       NamedSharding(mesh, tok_spec),
                                       NamedSharding(mesh, P())),
                     out_shardings=None,
                     donate_argnums=(1,))
    params_in = shl.sds_tree(shl.abstract_params(cfg, dtype), specs, mesh)
    cache_in = shl.sds_tree(cache_shapes, cache_specs, mesh)
    token = _sds((B,), jnp.int32, mesh, tok_spec)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    return BuiltStep(jitted, (params_in, cache_in, token, pos),
                     f"decode[{cfg.arch_id}/{shape.name}]")


def _infer_fsdp(cfg: ModelConfig, mesh: Mesh) -> bool:
    """Inference: FSDP the weights too once model-parallel alone would not fit
    comfortably (~> 4 GB/chip of the 16 GB v5e HBM)."""
    return cfg.param_count() * 2 / mesh.shape["model"] > 4e9


def build_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig, **kw) -> BuiltStep:
    cfg = cfg.for_shape(shape)
    if shape.kind == "train":
        return build_train_step(cfg, mesh, shape, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, mesh, shape)
    return build_decode_step(cfg, mesh, shape)


# ================================================================ MLMC train


def build_mlmc_train_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig,
                          mlmc_cfg, level: int,
                          *, aggregator: str = "cwmed", attack: str = "none",
                          delta: float = 0.25, opt: Optional[Optimizer] = None,
                          lr: float = 1e-3, agg_backend: str = "auto",
                          dtype=jnp.bfloat16) -> BuiltStep:
    """Algorithm 2 at MLMC level J=`level` in Mode B.

    One round computes three robust-aggregated gradients from nested slices of
    a (B·2^J)-sized per-worker batch — levels 0, J−1, J — then applies
    ``mlmc.mlmc_combine`` guarded by the fail-safe event E_t (Eq. 6), with
    ‖ĝ^J − ĝ^{J−1}‖ a global norm assembled via one scalar psum over the
    worker axes (``core.sharded.make_global_norm``). Beyond-cap levels
    (J > j_max) drop the correction, exactly like the Mode-A drivers.

    The step's third output is ``(loss, failsafe_ok, corr_norm)``: the mean
    loss over every row the step's gradients used (the level-J batch, or
    the level-0 prefix beyond the cap), the fail-safe event and
    ‖ĝ^J − ĝ^{J−1}‖.
    """
    from repro.core.mlmc import level_prefix, mlmc_combine
    from repro.core.sharded import make_global_norm

    j = level
    pl = _train_plumbing(cfg, mesh, shape, level_units=2 ** j,
                         aggregator=aggregator, attack=attack, delta=delta,
                         opt=opt, lr=lr, agg_backend=agg_backend, dtype=dtype)
    cfg = pl.cfg
    norm_fn = make_global_norm(pl.plans, pl.waxes)

    def step_local(params, opt_state, batch, maskf, widx):
        hook = make_param_hook(pl.byz, pl.plans, maskf, widx)

        def agg_grad(b):
            # local (per-worker) batch holds (B/m)·2^j rows; the level-n
            # slice is its nested prefix
            return jax.value_and_grad(
                lambda p: loss_fn(p, b, cfg, param_hook=hook))(params)

        loss, g0 = agg_grad(level_prefix(batch, 1, 2 ** j, axis=0))
        gjm1 = gj = None
        if 1 <= j <= mlmc_cfg.j_max:
            _, gjm1 = agg_grad(level_prefix(batch, 2 ** (j - 1), 2 ** j,
                                            axis=0))
            loss, gj = agg_grad(level_prefix(batch, 2 ** j, 2 ** j, axis=0))
        g, info = mlmc_combine(g0, gjm1, gj, j, mlmc_cfg, norm_fn=norm_fn)
        updates, opt_state = pl.opt.update(g, opt_state, params)
        params = apply_updates(params, updates)
        loss = jax.lax.pmean(loss, pl.waxes)
        ok = jax.lax.pmean(info["failsafe_ok"].astype(jnp.float32), pl.waxes)
        return params, opt_state, (loss, ok, info["corr_norm"])

    return _wrap_train_step(pl, step_local, mesh, (P(), P(), P()),
                            f"mlmc_train[{cfg.arch_id}/{shape.name}/J{j}]")
