"""Composable decoder stack covering all assigned families.

A model is a repeated *layer group* (``cfg.pattern()``): the stack lowers as a
single ``lax.scan`` over ``n_groups`` stacked parameter groups, so HLO size is
independent of depth (72–100 layer archs compile like 1-group models).

Three entry points, matching the assigned input shapes:
  * ``loss_fn``      — training step objective (train_4k)
  * ``prefill``      — forward + KV/state cache construction (prefill_32k)
  * ``decode_step``  — one token against a seq_len cache (decode_32k, long_500k)
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import ssm
from repro.models.layers import (
    apply_norm, rms_norm, rope_angles, apply_rope,
    chunked_attention, decode_attention, mlp,
)
from repro.models.moe import moe_ffn

Params = Dict[str, Any]

# ================================================================ init


def _norm_params(cfg, d, key=None):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def _dense(key, shape, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _attn_params(cfg: ModelConfig, key, dtype, cross=False) -> Params:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "ln": _norm_params(cfg, D),
        "wq": _dense(ks[0], (D, H * hd), dtype),
        "wk": _dense(ks[1], (D, KV * hd), dtype),
        "wv": _dense(ks[2], (D, KV * hd), dtype),
        "wo": _dense(ks[3], (H * hd, D), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _mlp_params(cfg, key, dtype, d_ff=None) -> Params:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w1": _dense(ks[0], (D, F), dtype), "w2": _dense(ks[1], (F, D), dtype)}
    if cfg.act == "swiglu":
        p["w3"] = _dense(ks[2], (D, F), dtype)
    return p


def _moe_params(cfg, key, dtype) -> Params:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": _dense(ks[0], (D, E), jnp.float32),
        "we1": _dense(ks[1], (E, D, F), dtype, scale=1.0 / math.sqrt(D)),
        "we2": _dense(ks[2], (E, F, D), dtype, scale=1.0 / math.sqrt(F)),
    }
    if cfg.act == "swiglu":
        p["we3"] = _dense(ks[3], (E, D, F), dtype, scale=1.0 / math.sqrt(D))
    if cfg.n_shared_experts:
        p["shared"] = _mlp_params(cfg, jax.random.fold_in(key, 7), dtype,
                                  d_ff=cfg.shared_d_ff)
    return p


def _mamba_params(cfg, key, dtype) -> Params:
    D = cfg.d_model
    di = cfg.mamba_expand * D
    ds = cfg.mamba_d_state
    dt_rank = max(1, D // 16)
    k = cfg.mamba_conv
    ks = jax.random.split(key, 5)
    return {
        "ln": _norm_params(cfg, D),
        "in_proj": _dense(ks[0], (D, 2 * di), dtype),
        "conv_w": _dense(ks[1], (k, di), dtype, scale=1.0 / math.sqrt(k)),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": _dense(ks[2], (di, dt_rank + 2 * ds), dtype),
        "dt_proj": _dense(ks[3], (dt_rank, di), dtype),
        "dt_bias": jnp.full((di,), math.log(math.e ** 0.01 - 1), jnp.float32),
        "A_log": jnp.log(jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _dense(ks[4], (di, D), dtype),
    }


def _rwkv_params(cfg, key, dtype) -> Params:
    D = cfg.d_model
    hd = cfg.rwkv_head_dim
    H = D // hd
    lr = 64
    ks = jax.random.split(key, 8)
    p = {"ln": _norm_params(cfg, D)}
    for i, n in enumerate(("wr", "wk", "wv", "wg", "wo")):
        p[n] = _dense(ks[i], (D, D), dtype)
    for n in ("mu_r", "mu_k", "mu_v", "mu_w", "mu_g"):
        p[n] = jnp.full((D,), 0.5, dtype)
    p["w0"] = jnp.full((D,), -2.0, jnp.float32)
    p["w1"] = _dense(ks[5], (D, lr), jnp.float32)
    p["w2"] = _dense(ks[6], (lr, D), jnp.float32, scale=0.01)
    p["u"] = jnp.zeros((D,), jnp.float32)
    p["ln_x"] = jnp.ones((D,), jnp.float32)
    return p


def _cmix_params(cfg, key, dtype) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "ln": _norm_params(cfg, D),
        "mu_k": jnp.full((D,), 0.5, dtype),
        "mu_r": jnp.full((D,), 0.5, dtype),
        "wk": _dense(ks[0], (D, F), dtype),
        "wv": _dense(ks[1], (F, D), dtype),
        "wr": _dense(ks[2], (D, D), dtype),
    }


def _block_params(cfg, mixer, mlp_kind, key, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {}
    if mixer in ("attn", "cross_attn"):
        p["mix"] = _attn_params(cfg, k1, dtype, cross=(mixer == "cross_attn"))
        if cfg.family == "audio":  # whisper decoder: self + cross per layer
            p["cross"] = _attn_params(cfg, k3, dtype, cross=True)
    elif mixer == "mamba":
        p["mix"] = _mamba_params(cfg, k1, dtype)
    elif mixer == "rwkv":
        p["mix"] = _rwkv_params(cfg, k1, dtype)
    if mlp_kind == "rwkv_cmix":
        p["mlp"] = _cmix_params(cfg, k2, dtype)
    else:
        q = {"ln": _norm_params(cfg, cfg.d_model)}
        if mlp_kind in ("moe", "moe+dense"):
            q["moe"] = _moe_params(cfg, k2, dtype)
            if mlp_kind == "moe+dense":
                q["dense"] = _mlp_params(cfg, jax.random.fold_in(k2, 3), dtype)
        else:
            q["dense"] = _mlp_params(cfg, k2, dtype)
        p["mlp"] = q
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    ke, ku, kb, kenc = jax.random.split(key, 4)
    D, V = cfg.d_model, cfg.vocab_size
    params: Params = {
        "embed": _dense(ke, (V, D), dtype, scale=0.02),
        "final_norm": _norm_params(cfg, D),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = _dense(ku, (D, V), dtype)
    pattern = cfg.pattern()

    def one_group(gkey):
        gks = jax.random.split(gkey, len(pattern))
        return {f"b{i}": _block_params(cfg, mixer, mk, gks[i], dtype)
                for i, (mixer, mk) in enumerate(pattern)}

    gkeys = jax.random.split(kb, cfg.n_groups)
    params["blocks"] = jax.vmap(one_group)(gkeys)

    if cfg.family == "audio":
        # encoder stack (bidirectional attn + mlp), stacked over enc layers
        def enc_layer(k):
            return {"attn": _attn_params(cfg, k, dtype),
                    "mlp": {"ln": _norm_params(cfg, D),
                            "dense": _mlp_params(cfg, jax.random.fold_in(k, 1), dtype)}}
        eks = jax.random.split(kenc, cfg.n_encoder_layers)
        params["encoder"] = {"blocks": jax.vmap(enc_layer)(eks),
                             "final_norm": _norm_params(cfg, D)}
        params["dec_pos"] = _dense(jax.random.fold_in(kenc, 2), (32768, D), dtype, scale=0.02)
    return params


# ================================================================ blocks


def _attn_apply(x, p, cfg: ModelConfig, *, cross=False, kv_src=None, causal=True,
                pos_offset=0, cache=None, pos=None, mode="train", pad_to=0):
    """Returns (x_out, cache_out). cache_out: prefill -> new kv; decode -> updated."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = apply_norm(x, p["ln"], cfg.norm)
    q = h @ p["wq"] + (p["bq"] if "bq" in p else 0)
    q = q.reshape(B, S, H, hd)
    use_rope = cfg.family != "audio" and not cross

    if cross and mode == "decode":
        ck, cv = cache["k"], cache["v"]
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"])
        out = decode_attention(q, ck, cv)
        new_cache = cache
    else:
        src = kv_src if cross else h
        k = (src @ p["wk"] + (p["bk"] if "bk" in p else 0)).reshape(B, -1, KV, hd)
        v = (src @ p["wv"] + (p["bv"] if "bv" in p else 0)).reshape(B, -1, KV, hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"])
            k = rms_norm(k, p["k_norm"])
        if mode == "decode":
            # self-attention, single token against ring-buffer cache
            Sc = cache["k"].shape[1]
            if use_rope:
                cos, sin = rope_angles(pos[None], hd, cfg.rope_theta)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            idx = (pos % Sc).astype(jnp.int32)
            kc = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, idx, 0, 0))
            vc = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, idx, 0, 0))
            valid = jnp.arange(Sc) < jnp.minimum(pos + 1, Sc)
            out = decode_attention(q, kc, vc, valid[None].repeat(B, 0))
            new_cache = {"k": kc, "v": vc}
        else:
            if use_rope:
                cos, sin = rope_angles(pos_offset + jnp.arange(S), hd, cfg.rope_theta)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            window = 0 if cross else cfg.sliding_window
            if cfg.attn_impl == "flash":
                from repro.models.flash import flash_attention
                out = flash_attention(q, k, v, causal and not cross, window,
                                      0, 1024, cfg.attn_seq_shard,
                                      cfg.attn_batch_shard)
            else:
                out = chunked_attention(q, k, v, causal=causal and not cross,
                                        window=window)
            new_cache = None
            if mode == "prefill":
                if cfg.sliding_window and not cross:
                    wk = k[:, -cfg.sliding_window:]
                    wv = v[:, -cfg.sliding_window:]
                    new_cache = {"k": wk, "v": wv}
                else:
                    if pad_to and not cross and pad_to > k.shape[1]:
                        padw = ((0, 0), (0, pad_to - k.shape[1]), (0, 0), (0, 0))
                        k, v = jnp.pad(k, padw), jnp.pad(v, padw)
                    new_cache = {"k": k, "v": v}
    out = out.reshape(B, S, H * hd) @ p["wo"]
    return x + out, new_cache


def _mlp_apply(x, p, cfg: ModelConfig, mlp_kind, *, cache=None, mode="train"):
    """Returns (x_out, aux, cache_out)."""
    aux = jnp.zeros((), jnp.float32)
    if mlp_kind == "rwkv_cmix":
        h = apply_norm(x, p["ln"], cfg.norm)
        dcache = cache if mode == "decode" else None
        out, new_cache = ssm.rwkv_channel_mix(h, p, dcache)
        if mode == "train":
            new_cache = None
        return x + out, aux, new_cache
    h = apply_norm(x, p["ln"], cfg.norm)
    out = 0.0
    if "moe" in p:
        moe_out, aux = moe_ffn(h, p["moe"], top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor, act=cfg.act,
                               token_group=cfg.moe_token_group,
                               expert_shard=cfg.moe_expert_shard)
        out = out + moe_out
        if "shared" in p["moe"]:
            out = out + mlp(h, p["moe"]["shared"], cfg.act)
    if "dense" in p:
        out = out + mlp(h, p["dense"], cfg.act)
    return x + out, aux, None


def _block_apply(x, p, cfg: ModelConfig, mixer, mlp_kind, *, kv_src=None,
                 cache=None, pos=None, pos_offset=0, mode="train", pad_to=0):
    """Returns (x, aux, cache_out)."""
    cache = cache or {}
    cache_out = {}
    if mixer in ("attn", "cross_attn"):
        is_cross = mixer == "cross_attn"
        x, c = _attn_apply(x, p["mix"], cfg, cross=is_cross, kv_src=kv_src,
                           pos_offset=pos_offset, cache=cache.get("mix"),
                           pos=pos, mode=mode, pad_to=pad_to)
        if c is not None:
            cache_out["mix"] = c
        if cfg.family == "audio":  # whisper decoder adds cross-attn
            x, c2 = _attn_apply(x, p["cross"], cfg, cross=True, kv_src=kv_src,
                                cache=cache.get("cross"), pos=pos, mode=mode)
            if c2 is not None:
                cache_out["cross"] = c2
    elif mixer == "mamba":
        h = apply_norm(x, p["mix"]["ln"], cfg.norm)
        dcache = cache.get("mix") if mode == "decode" else None
        out, c = ssm.mamba_mixer(h, p["mix"], cfg, cache=dcache)
        if mode in ("decode", "prefill"):
            cache_out["mix"] = jax.tree.map(lambda a: a, c)
        x = x + out
    elif mixer == "rwkv":
        h = apply_norm(x, p["mix"]["ln"], cfg.norm)
        dcache = cache.get("mix") if mode == "decode" else None
        out, c = ssm.rwkv_time_mix(h, p["mix"], cfg, cache=dcache)
        if mode in ("decode", "prefill"):
            cache_out["mix"] = c
        x = x + out
    x, aux, c = _mlp_apply(x, p["mlp"], cfg, mlp_kind,
                           cache=cache.get("mlp"), mode=mode)
    if c is not None:
        cache_out["mlp"] = c
    return x, aux, cache_out


# ================================================================ stacks


def _encoder_forward(params, frames, cfg: ModelConfig):
    """Whisper encoder over stubbed frame embeddings (B, Senc, D)."""
    S = frames.shape[1]
    D = cfg.d_model
    # sinusoidal positions
    pos = jnp.arange(S)[:, None]
    dim = jnp.arange(D // 2)[None, :]
    ang = pos / (10000 ** (2 * dim / D))
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(frames.dtype)
    x = frames + pe[None]

    def body(x, p):
        x, _ = _attn_apply(x, p["attn"], cfg, causal=False, mode="train")
        x, _, _ = _mlp_apply(x, p["mlp"], cfg, "dense", mode="train")
        return x, None

    x, _ = lax.scan(body, x, params["encoder"]["blocks"])
    return apply_norm(x, params["encoder"]["final_norm"], cfg.norm)


def _embed_tokens(params, tokens, cfg, pos=None):
    x = params["embed"][tokens]
    if cfg.family == "audio":
        if pos is not None:  # decode: single absolute position
            x = x + lax.dynamic_slice_in_dim(params["dec_pos"], pos, 1, 0)[None]
        else:
            S = tokens.shape[1]
            x = x + params["dec_pos"][:S][None]
    return x


def _unembed(params, x, cfg):
    x = apply_norm(x, params["final_norm"], cfg.norm)
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return x @ w


def _kv_src(params, cfg, extra):
    if cfg.family == "audio":
        return _encoder_forward(params, extra["frames"], cfg)
    if cfg.family == "vlm":
        return extra["patches"]
    return None


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig,
            extra: Optional[dict] = None, mode: str = "train",
            remat: bool = True, pad_to: int = 0, param_hook=None):
    """Full causal forward. Returns (logits, aux) in train mode, and
    (logits, aux, cache) in prefill mode.

    ``param_hook(subtree, scope)`` — optional transform applied to parameters
    at point of use (scope 'top' once; scope 'blocks' per scanned group).
    Mode B threads the robust-aggregating FSDP all-gather through this."""
    if param_hook is not None:
        top = {k: v for k, v in params.items() if k != "blocks"}
        params = {**param_hook(top, "top"), "blocks": params["blocks"]}
    x = _embed_tokens(params, tokens, cfg)
    kv_src = _kv_src(params, cfg, extra or {})
    pattern = cfg.pattern()

    def _stream_constraint(x):
        # keep the residual stream (batch, seq)-sharded so per-layer XLA
        # choices can't silently replicate it (§Perf iteration 2)
        if cfg.attn_seq_shard or cfg.attn_batch_shard:
            from repro.models.flash import _maybe_shard
            x = _maybe_shard(x, (cfg.attn_batch_shard or None,
                                 cfg.attn_seq_shard or None, None))
        return x

    def group_body(x, gp):
        if param_hook is not None:
            gp = param_hook(gp, "blocks")
        aux = jnp.zeros((), jnp.float32)
        caches = {}
        x = _stream_constraint(x)
        for i, (mixer, mk) in enumerate(pattern):
            x, a, c = _block_apply(x, gp[f"b{i}"], cfg, mixer, mk,
                                   kv_src=kv_src, mode=mode, pad_to=pad_to)
            aux = aux + a
            # jaxlint: disable=JXL002 -- c is a host dict of cache leaves; its truthiness is static pytree structure, not a traced value
            if c:
                caches[f"b{i}"] = c
        return x, (aux, caches)

    body = group_body
    if remat and mode == "train":
        body = jax.checkpoint(group_body, prevent_cse=False)
    x, (auxs, caches) = lax.scan(body, x, params["blocks"])
    logits = _unembed(params, x, cfg)
    aux = jnp.sum(auxs)
    if mode == "prefill":
        return logits, aux, caches
    return logits, aux


def loss_fn(params: Params, batch: dict, cfg: ModelConfig, param_hook=None) -> jax.Array:
    """Mean next-token cross-entropy + router aux."""
    logits, aux = forward(params, batch["tokens"], cfg, extra=batch.get("extra"),
                          param_hook=param_hook)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - gold).mean()
    return nll + cfg.router_aux_weight * aux


# ---------------------------------------------------------------- serving


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16) -> Params:
    """Cache pytree for decode; leaves stacked over n_groups."""
    KV, hd, D = cfg.n_kv_heads, cfg.hd, cfg.d_model
    S_eff = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len

    def one_group():
        c = {}
        for i, (mixer, mk) in enumerate(cfg.pattern()):
            e = {}
            if mixer == "attn":
                e["mix"] = {"k": jnp.zeros((batch, S_eff, KV, hd), dtype),
                            "v": jnp.zeros((batch, S_eff, KV, hd), dtype)}
                if cfg.family == "audio":
                    e["cross"] = {"k": jnp.zeros((batch, cfg.encoder_seq, KV, hd), dtype),
                                  "v": jnp.zeros((batch, cfg.encoder_seq, KV, hd), dtype)}
            elif mixer == "cross_attn":
                e["mix"] = {"k": jnp.zeros((batch, cfg.n_image_tokens, KV, hd), dtype),
                            "v": jnp.zeros((batch, cfg.n_image_tokens, KV, hd), dtype)}
            elif mixer == "mamba":
                di = cfg.mamba_expand * D
                e["mix"] = {"conv": jnp.zeros((batch, cfg.mamba_conv - 1, di), dtype),
                            "ssm": jnp.zeros((batch, di, cfg.mamba_d_state), jnp.float32)}
            elif mixer == "rwkv":
                H = D // cfg.rwkv_head_dim
                e["mix"] = {"prev": jnp.zeros((batch, D), dtype),
                            "state": jnp.zeros((batch, H, cfg.rwkv_head_dim,
                                                cfg.rwkv_head_dim), jnp.float32)}
            if mk == "rwkv_cmix":
                e["mlp"] = {"prev": jnp.zeros((batch, D), dtype)}
            c[f"b{i}"] = e
        return c

    one = one_group()
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (cfg.n_groups,) + x.shape), one)


def decode_step(params: Params, cache: Params, token: jax.Array, pos: jax.Array,
                cfg: ModelConfig) -> Tuple[jax.Array, Params]:
    """One serving step. token: (B,) int32; pos: scalar int32 (tokens so far).

    Returns (logits (B, V), new_cache)."""
    x = _embed_tokens(params, token[:, None], cfg, pos=pos)
    pattern = cfg.pattern()

    def group_body(x, gp_cache):
        gp, gc = gp_cache
        new_c = {}
        for i, (mixer, mk) in enumerate(pattern):
            x, _, c = _block_apply(x, gp[f"b{i}"], cfg, mixer, mk,
                                   cache=gc.get(f"b{i}", {}), pos=pos, mode="decode")
            new_c[f"b{i}"] = c
        return x, new_c

    x, new_cache = lax.scan(group_body, x, (params["blocks"], cache))
    logits = _unembed(params, x, cfg)
    return logits[:, 0], new_cache


def prefill(params: Params, tokens: jax.Array, cfg: ModelConfig,
            extra: Optional[dict] = None, pad_to: int = 0):
    """Prefill pass: returns (last-position logits, cache).

    ``pad_to`` grows self-attention KV caches to this many slots so that
    subsequent ``decode_step`` calls append instead of ring-overwriting."""
    logits, _, cache = forward(params, tokens, cfg, extra=extra, mode="prefill",
                               pad_to=pad_to)
    return logits[:, -1], cache
