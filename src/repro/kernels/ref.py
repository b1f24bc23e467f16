"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` function defines the exact semantics its kernel must match
(tests sweep shapes/dtypes and assert_allclose kernel-vs-ref).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.afpm import AFPMConfig, afpm_mult_f32


def split_hi_lo_ref(x: jax.Array):
    """fp32 -> (hi, lo) bf16 segments; hi = RNE bf16, lo = bf16(x - hi).

    Both roundings are ``lax.reduce_precision``, not an f32 -> bf16 -> f32
    round trip: XLA may keep such a pair in f32 as excess precision, and on
    a TPU v5e it did, which zeroed lo and made every pass level compute
    hi(x) @ hi(w)."""
    x = jnp.asarray(x, jnp.float32)
    to_bf16 = functools.partial(jax.lax.reduce_precision, exponent_bits=8,
                                mantissa_bits=7)
    hi = to_bf16(x)
    return hi.astype(jnp.bfloat16), to_bf16(x - hi).astype(jnp.bfloat16)


def afpm_matmul_ref(x: jax.Array, w: jax.Array, passes: int = 3) -> jax.Array:
    """Segmented (split-float) approximate matmul oracle.

    passes=3: AC + AD + BC (BD omitted — the paper's Eq. 6 on the MXU)
    passes=2: AC + AD (weight low bits dropped)
    passes=1: AC only (ACL-like)
    """
    xh, xl = split_hi_lo_ref(x)
    wh, wl = split_hi_lo_ref(w)
    dot = lambda a, b: jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    out = dot(xh, wh)
    if passes >= 2:
        out = out + dot(xl, wh)
    if passes >= 3:
        out = out + dot(xh, wl)
    return out


def afpm_bitwise_ref(x: jax.Array, y: jax.Array, cfg: AFPMConfig) -> jax.Array:
    """Elementwise bit-level AFPM oracle — the core datapath itself."""
    return afpm_mult_f32(x, y, cfg)


def ssd_scan_ref(x, dt, A, B, C, chunk: int = 64):
    """Mamba2 SSD (state-space dual) chunked scan oracle.

    Shapes (single head group for the oracle):
      x:  (L, H, P)   inputs per head
      dt: (L, H)      positive step sizes
      A:  (H,)        negative state decay per head
      B:  (L, N)      input->state projection (shared across heads, "G" groups=1)
      C:  (L, N)      state->output projection
    Returns y: (L, H, P).

    Reference semantics: per head h, state S (N, P):
      S_t = exp(A_h * dt_t) * S_{t-1} + dt_t * B_t^T (x_t scaled)
      y_t = C_t S_t
    computed with a plain sequential scan (the kernel blocks it by chunks).
    """
    L, H, P = x.shape
    N = B.shape[-1]

    def head(xh, dth, Ah):
        # xh: (L, P), dth: (L,)
        decay = jnp.exp(Ah * dth)  # (L,)

        def step(S, t):
            xt, dt_t, dec, Bt, Ct = t
            S = dec * S + dt_t * (Bt[:, None] * xt[None, :])  # (N, P)
            y = Ct @ S  # (P,)
            return S, y

        S0 = jnp.zeros((N, P), jnp.float32)
        _, ys = jax.lax.scan(step, S0, (xh, dth, decay, B, C))
        return ys  # (L, P)

    y = jax.vmap(head, in_axes=(1, 1, 0), out_axes=1)(
        x.astype(jnp.float32), dt.astype(jnp.float32), A.astype(jnp.float32)
    )
    return y


def chunk_decay(dt, A, chunk: int):
    """Per-chunk log cumulative decay ``l[t] = A_h * cumsum(dt)[t]`` (the
    cumsum restarting at every chunk boundary).

    Hoisted out of both SSD execution paths on purpose: computed *inside*
    a fused kernel/scan body, ``A * cumsum(dt)`` is subject to
    fusion-context-dependent FP contraction (the compiler may emit
    ``fma(A, cs_t, -A*cs_s)`` for ``l_t - l_s`` in one lowering and two
    rounded multiplies in another), which made interpret-vs-xla agreement
    shape-dependent at small chunks.  Computing the decay once, behind a
    materialization boundary, pins its bits so both paths consume
    identical values.

    dt: (L, H), A: (H,) -> l: (L, H); L must be a multiple of ``chunk``.
    """
    L, H = dt.shape
    assert L % chunk == 0, (L, chunk)
    dtc = dt.astype(jnp.float32).reshape(L // chunk, chunk, H)
    l = A.astype(jnp.float32)[None, None, :] * jnp.cumsum(dtc, axis=1)
    return l.reshape(L, H)


def ssd_scan_chunked_ref(x, dt, A, B, C, chunk: int = 128):
    """Chunked SSD in pure jnp — the same math/FLOP structure as the Pallas
    kernel (used as the CPU/XLA execution path so dry-run cost analysis
    reflects the chunked algorithm, and as a second oracle in tests).

    Bit-exact with the interpret-mode Pallas kernel: both consume the
    same hoisted :func:`chunk_decay` and do the same per-chunk dots.
    """
    L, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    lfull = chunk_decay(dt, A, Q)
    Bc = B.astype(jnp.float32).reshape(nc, Q, N)
    Cc = C.astype(jnp.float32).reshape(nc, Q, N)
    t_idx = jnp.arange(Q)[:, None]
    s_idx = jnp.arange(Q)[None, :]

    def head(xh, dth, lh):
        xc = xh.reshape(nc, Q, P)
        dtc = dth.reshape(nc, Q)
        lc = lh.reshape(nc, Q)

        def chunk_body(S, inp):
            xq, dq, l, Bq, Cq = inp
            CB = Cq @ Bq.T
            # clamp: only t>=s is used, where l_t - l_s <= 0; the clamp keeps
            # the masked upper triangle finite (inf would NaN the where-grad)
            ratio = jnp.exp(jnp.minimum(l[:, None] - l[None, :], 0.0))
            M = jnp.where(t_idx >= s_idx, CB * ratio * dq[None, :], 0.0)
            y = M @ xq + (Cq * jnp.exp(l)[:, None]) @ S
            w = dq * jnp.exp(l[-1] - l)
            S_new = jnp.exp(l[-1]) * S + (Bq * w[:, None]).T @ xq
            return S_new, y

        S0 = jnp.zeros((N, P), jnp.float32)
        _, ys = jax.lax.scan(chunk_body, S0, (xc, dtc, lc, Bc, Cc))
        return ys.reshape(L, P)

    return jax.vmap(head, in_axes=(1, 1, 1), out_axes=1)(x, dt, lfull)
