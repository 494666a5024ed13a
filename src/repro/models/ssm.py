"""State-space mixers: Mamba (S6 selective scan) and RWKV-6 (Finch) time-mix.

Mamba's selective scan is chunked: a sequential ``lax.scan`` over sequence
chunks carrying the SSM state, with a parallel ``associative_scan`` inside
each chunk — this bounds the (B, L, d_inner, d_state) temporaries to
(B, chunk, d_inner, d_state) while keeping log-depth within the chunk.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


from repro.models.layers import group_norm_heads

# ================================================================ Mamba


def _ssm_combine(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a1 * a2, a2 * b1 + b2


def selective_scan(x, delta, A, B, C, D, h0=None, chunk: int = 256):
    """h_t = exp(dt*A) h_{t-1} + dt*B_t*x_t ; y_t = C_t . h_t + D*x_t.

    x, delta: (Bt, L, di); A: (di, ds); B, C: (Bt, L, ds); D: (di,).
    Returns (y (Bt,L,di), h_last (Bt,di,ds)).
    """
    Bt, L, di = x.shape
    ds = A.shape[1]
    chunk = min(chunk, L)
    Lp = -(-L // chunk) * chunk
    pad = Lp - L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        delta = jnp.pad(delta, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    nc = Lp // chunk
    xs = x.reshape(Bt, nc, chunk, di)
    dts = delta.reshape(Bt, nc, chunk, di)
    Bs = B.reshape(Bt, nc, chunk, ds)
    Cs = C.reshape(Bt, nc, chunk, ds)
    if h0 is None:
        h0 = jnp.zeros((Bt, di, ds), jnp.float32)

    # remat the chunk body: autodiff of the scan would otherwise save the
    # (Bt, chunk, di, ds) discretized a/b/h_all temporaries for every chunk —
    # the dominant train-memory term for mamba archs (§Perf iteration 4).
    # With checkpointing only the (Bt, di, ds) carry is kept per chunk.
    @jax.checkpoint
    def body_fn(h, chunk_in):
        xc, dt, Bc, Cc = (t.astype(jnp.float32) for t in chunk_in)
        a = jnp.exp(dt[..., None] * A[None, None])  # (Bt, c, di, ds)
        b = (dt * xc)[..., None] * Bc[:, :, None, :]
        ca, cb = lax.associative_scan(_ssm_combine, (a, b), axis=1)
        h_all = ca * h[:, None] + cb  # (Bt, c, di, ds)
        y = jnp.einsum("bcds,bcs->bcd", h_all, Cc) + D[None, None] * xc
        return h_all[:, -1], y.astype(x.dtype)

    def body(h, ci):
        return body_fn(h, (xs[:, ci], dts[:, ci], Bs[:, ci], Cs[:, ci]))

    h_last, ys = lax.scan(body, h0, jnp.arange(nc))
    y = ys.transpose(1, 0, 2, 3).reshape(Bt, Lp, di)[:, :L]
    return y, h_last


def selective_step(x, delta, A, B, C, D, h):
    """Single decode step. x/delta: (Bt, di); B/C: (Bt, ds); h: (Bt, di, ds)."""
    xf = x.astype(jnp.float32)
    dt = delta.astype(jnp.float32)
    a = jnp.exp(dt[..., None] * A[None])
    b = (dt * xf)[..., None] * B[:, None, :].astype(jnp.float32)
    h = a * h + b
    y = jnp.einsum("bds,bs->bd", h, C.astype(jnp.float32)) + D[None] * xf
    return y.astype(x.dtype), h


def _causal_conv(x, w, b):
    """Depthwise causal conv. x: (Bt, L, di), w: (k, di) -> (Bt, L, di)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = lax.conv_general_dilated(
        xp, w[:, None, :],  # (k, 1, di) kernel: (spatial, in_per_group, out)
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=x.shape[-1])
    return out + b


def mamba_mixer(x, p, cfg, cache=None, pos=None):
    """Mamba block. x: (Bt, L, D). cache: dict(conv (Bt,k-1,di), ssm (Bt,di,ds))
    for decode (L==1). Returns (y, new_cache)."""
    Bt, L, Dm = x.shape
    di = cfg.mamba_expand * cfg.d_model
    ds = cfg.mamba_d_state
    xz = x @ p["in_proj"]  # (Bt, L, 2*di)
    xi_raw, z = jnp.split(xz, 2, axis=-1)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (di, ds)
    if cache is None:
        xi = jax.nn.silu(_causal_conv(xi_raw, p["conv_w"], p["conv_b"]))
        dbc = xi @ p["x_proj"]  # (Bt, L, dt_rank + 2*ds)
        dt_rank = p["dt_proj"].shape[0]
        dt, Bssm, Cssm = jnp.split(dbc, [dt_rank, dt_rank + ds], axis=-1)
        delta = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])
        y, h_last = selective_scan(xi, delta, A, Bssm, Cssm, p["D"])
        # prefill cache: conv state = last k-1 raw conv inputs, ssm = final state
        k = p["conv_w"].shape[0]
        tail = xi_raw[:, -(k - 1):]
        pad = (k - 1) - tail.shape[1]
        if pad > 0:
            tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
        new_cache = {"conv": tail, "ssm": h_last}
    else:
        # decode: L == 1
        conv_st = cache["conv"].astype(xi_raw.dtype)  # (Bt, k-1, di)
        xin = jnp.concatenate([conv_st, xi_raw], axis=1)  # (Bt, k, di)
        xc = jnp.einsum("bkd,kd->bd", xin, p["conv_w"]) + p["conv_b"]
        xc = jax.nn.silu(xc)
        dbc = xc @ p["x_proj"]
        dt_rank = p["dt_proj"].shape[0]
        dt, Bssm, Cssm = jnp.split(dbc, [dt_rank, dt_rank + ds], axis=-1)
        delta = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])
        yb, h = selective_step(xc, delta, A, Bssm, Cssm, p["D"], cache["ssm"])
        y = yb[:, None]
        new_cache = {"conv": xin[:, 1:], "ssm": h}
    y = y * jax.nn.silu(z)
    return y @ p["out_proj"], new_cache


# ================================================================ RWKV-6


def _rwkv_decay(xw, p):
    """Data-dependent per-channel decay: w = exp(-exp(w0 + tanh(x@w1)@w2))."""
    lora = jnp.tanh(xw.astype(jnp.float32) @ p["w1"]) @ p["w2"]
    return jnp.exp(-jnp.exp(p["w0"] + lora))  # (..., D) in (0,1)


def _rwkv_wkv_scan(r, k, v, w, u, s0):
    """Sequential wkv. r/k/v/w: (Bt, L, H, hd); u: (H, hd); s0: (Bt, H, hd, hd).

    S_t = diag(w_t) S_{t-1} + k_t (x) v_t
    y_t = r_t . (S_{t-1} + diag(u) k_t (x) v_t)
    """
    def body(S, inp):
        rt, kt, vt, wt = inp  # (Bt, H, hd)
        kv = kt[..., :, None] * vt[..., None, :]  # (Bt, H, hd, hd)
        y = jnp.einsum("bhi,bhij->bhj", rt, S + u[None, :, :, None] * kv)
        S = wt[..., :, None] * S + kv
        return S, y

    seq = (r.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
           v.transpose(1, 0, 2, 3), w.transpose(1, 0, 2, 3))
    S, ys = lax.scan(body, s0, seq)
    return ys.transpose(1, 0, 2, 3), S  # (Bt, L, H, hd)


def rwkv_time_mix(x, p, cfg, cache=None):
    """RWKV-6 time mixing. x: (Bt, L, D) post-norm input.

    cache (decode): dict(prev (Bt, D), state (Bt, H, hd, hd)).
    Returns (y, new_cache).
    """
    Bt, L, Dm = x.shape
    hd = cfg.rwkv_head_dim
    H = Dm // hd
    if cache is None:
        prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        s0 = jnp.zeros((Bt, H, hd, hd), jnp.float32)
    else:
        prev = cache["prev"][:, None]
        s0 = cache["state"]
    d = prev - x
    xr = x + d * p["mu_r"]
    xk = x + d * p["mu_k"]
    xv = x + d * p["mu_v"]
    xw = x + d * p["mu_w"]
    xg = x + d * p["mu_g"]
    r = (xr @ p["wr"]).reshape(Bt, L, H, hd)
    k = (xk @ p["wk"]).reshape(Bt, L, H, hd)
    v = (xv @ p["wv"]).reshape(Bt, L, H, hd)
    g = jax.nn.silu(xg @ p["wg"])
    w = _rwkv_decay(xw, p).reshape(Bt, L, H, hd)
    u = p["u"].reshape(H, hd)
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
    y, S = _rwkv_wkv_scan(rf, kf, vf, wf, u, s0)
    y = group_norm_heads(y, p["ln_x"].reshape(H, hd)).reshape(Bt, L, Dm)
    y = (y.astype(x.dtype) * g) @ p["wo"]
    new_cache = {"prev": x[:, -1], "state": S}
    return y, new_cache


def rwkv_channel_mix(x, p, cache=None):
    """RWKV channel mix. x: (Bt, L, D). cache: dict(prev (Bt,D))."""
    if cache is None:
        prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    else:
        prev = cache["prev"][:, None]
    d = prev - x
    xk = x + d * p["mu_k"]
    xr = x + d * p["mu_r"]
    k = jnp.square(jax.nn.relu(xk @ p["wk"]))
    out = jax.nn.sigmoid(xr @ p["wr"]) * (k @ p["wv"])
    return out, {"prev": x[:, -1]}
