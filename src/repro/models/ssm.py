"""Mamba2 (SSD) block: projections + causal conv + chunked selective scan.

The scan itself is the Pallas kernel (``repro.kernels.ssd_scan``); this
module provides the block around it (in/out projections through the
paper's numerics config, gating, depthwise causal conv) plus the O(1)
single-token decode path that makes `long_500k` run at constant cost.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.numerics import (Numerics, layer_scope, maybe_numerics_scope,
                            nmatmul, resolve_here)
from repro.distributed.sharding import logical_constraint
from repro.kernels import ops

from .layers import PP, dense_init, normal, rmsnorm, rmsnorm_init


def ssm_dims(cfg):
    s = cfg.ssm
    d_inner = s.expansion * cfg.d_model
    n_heads = d_inner // s.head_dim
    return d_inner, n_heads


def ssm_init(key, cfg):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, H = ssm_dims(cfg)
    N = s.state_size
    ks = jax.random.split(key, 6)
    # fused in_proj: [z, x, B, C, dt]
    proj_out = 2 * d_inner + 2 * N + H
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "in_proj": dense_init(ks[0], d, proj_out, ("embed", "ssm_inner"), dt),
        "conv_w": PP(normal(ks[1], (s.conv_width, d_inner), (s.conv_width) ** -0.5, dt),
                     ("conv", "ssm_inner")),
        "conv_b": PP(jnp.zeros((d_inner,), jnp.float32), ("ssm_inner",)),
        "A_log": PP(jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)), (None,)),
        "dt_bias": PP(jnp.zeros((H,), jnp.float32), (None,)),
        "norm": rmsnorm_init(d_inner)["scale"],
        "out_proj": dense_init(ks[2], d_inner, d, ("ssm_inner", "embed"), dt),
    }


def _split_proj(proj, cfg):
    s = cfg.ssm
    d_inner, H = ssm_dims(cfg)
    N = s.state_size
    z = proj[..., :d_inner]
    xs = proj[..., d_inner:2 * d_inner]
    B = proj[..., 2 * d_inner:2 * d_inner + N]
    C = proj[..., 2 * d_inner + N:2 * d_inner + 2 * N]
    dt = proj[..., 2 * d_inner + 2 * N:]
    return z, xs, B, C, dt


def _causal_conv(xs, w, b, state=None):
    """Depthwise causal conv, width W.  xs: (B, S, D), w: (W, D).

    state: (B, W-1, D) trailing context for decode; returns (out, new_state).
    """
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros(xs.shape[:1] + (W - 1,) + xs.shape[2:], xs.dtype)
        full = jnp.concatenate([pad, xs], axis=1)
    else:
        full = jnp.concatenate([state.astype(xs.dtype), xs], axis=1)
    out = sum(full[:, i:i + xs.shape[1]] * w[i].astype(xs.dtype) for i in range(W))
    out = out + b.astype(xs.dtype)
    new_state = full[:, -(W - 1):]
    return jax.nn.silu(out), new_state


def ssm_apply(params, x, cfg, ncfg: Numerics | None = None, cache=None,
              want_state=False):
    """x: (B, S, D).  cache = dict(conv (B,W-1,Din), state (B,H,N,P)).

    want_state=True (prefill): additionally returns the final SSM/conv state,
    computed in closed form (one weighted einsum over the sequence).

    Numerics come from the ambient scope (the caller establishes this
    block's ``ssm`` prefix); relative call-site paths are
    ``in_proj``/``out_proj`` (projection matmuls) and ``scan`` (backend
    selection only — the selective scan is not a multiplier datapath, but
    its kernel backend is still per-layer).  ``ncfg`` optionally
    establishes the scope for this call.
    """
    with maybe_numerics_scope(ncfg):
        return _ssm_apply(params, x, cfg, cache=cache, want_state=want_state)


def _ssm_apply(params, x, cfg, cache=None, want_state=False):
    s = cfg.ssm
    B_, S, D = x.shape
    d_inner, H = ssm_dims(cfg)
    N, P = s.state_size, s.head_dim

    with layer_scope("in_proj"):
        proj = nmatmul(x, params["in_proj"]).astype(x.dtype)
    proj = logical_constraint(proj, ("batch", None, "ssm_inner"))
    z, xs, Bm, Cm, dt = _split_proj(proj, cfg)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"].astype(jnp.float32))  # (H,)

    if cache is None:
        xs_raw = xs
        xs, conv_tail = _causal_conv(xs, params["conv_w"], params["conv_b"])
        xh = xs.reshape(B_, S, H, P)
        scan_backend = resolve_here("scan").backend
        y = jax.vmap(
            lambda xb, db, Bb, Cb: ops.ssd_scan(xb, db, A, Bb, Cb, chunk=s.chunk,
                                                backend=scan_backend)
        )(xh, dt, Bm.astype(jnp.float32), Cm.astype(jnp.float32))
        new_cache = None
        if want_state:
            # closed-form final state:
            # S[h] = sum_l dt[l,h] e^{A_h (cum[L,h]-cum[l,h])} B[l] x[l,h]^T
            cum = jnp.cumsum(dt, axis=1)                         # (B,S,H)
            w = dt * jnp.exp(A[None, None, :] * (cum[:, -1:, :] - cum))
            S_fin = jnp.einsum("bsh,bsn,bshp->bhnp", w,
                               Bm.astype(jnp.float32), xh.astype(jnp.float32))
            new_cache = {
                "conv": xs_raw[:, -(s.conv_width - 1):].astype(x.dtype),
                "state": S_fin,
            }
    else:
        # decode: single token, O(1) state update
        xs, conv_state = _causal_conv(xs, params["conv_w"], params["conv_b"],
                                      state=cache["conv"])
        xh = xs.reshape(B_, 1, H, P).astype(jnp.float32)
        dt1 = dt[:, 0]                      # (B, H)
        decay = jnp.exp(A[None, :] * dt1)   # (B, H)
        Bv = Bm[:, 0].astype(jnp.float32)   # (B, N)
        Cv = Cm[:, 0].astype(jnp.float32)   # (B, N)
        S_prev = cache["state"]             # (B, H, N, P)
        inp = dt1[..., None, None] * Bv[:, None, :, None] * xh[:, 0][:, :, None, :]
        S_new = decay[..., None, None] * S_prev + inp
        y = jnp.einsum("bn,bhnp->bhp", Cv, S_new)[:, None]  # (B,1,H,P)
        y = y.reshape(B_, 1, H, P)
        new_cache = {"conv": conv_state.astype(cache["conv"].dtype), "state": S_new}

    y = y.reshape(B_, S, d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm({"scale": params["norm"]}, y, cfg.norm_eps)
    with layer_scope("out_proj"):
        return nmatmul(y, params["out_proj"]).astype(x.dtype), new_cache


def ssm_cache_init(cfg, batch, dtype=jnp.float32):
    s = cfg.ssm
    d_inner, H = ssm_dims(cfg)
    return {
        "conv": jnp.zeros((batch, s.conv_width - 1, d_inner), dtype),
        "state": jnp.zeros((batch, H, s.state_size, s.head_dim), jnp.float32),
    }
