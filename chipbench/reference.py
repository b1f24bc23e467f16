"""The plain reference: a float32 ``jax.numpy`` forward of a dense
SwiGLU/GQA decoder, written from the published description (Qwen3,
Mistral) and independent of ``repro``.

    x = E[tokens];  per layer:
        h = rms(x) * g1;  q, k, v = h Wq, h Wk, h Wv   (q, k: rms over
        head_dim first when qk_norm);  RoPE (rotate-half);  causal softmax
        attention, GQA;  x += o Wo
        h = rms(x) * g2;  x += (silu(h Wg) * (h Wi)) W2
    logits = rms(x) * gf  E^T (tied) or  W_unembed (untied)

Every matmul runs at ``Precision.HIGHEST``; activations stay float32.  The
weights are the benchmark's seeded bf16 draws (``chipbench/model.py``),
read in the published semantics: ``E = sqrt(d) * stored table`` and norm
weights ``1 + stored``.  Layers run one at a time and attention in query
blocks, so that the forward fits beside the weights.

``quant="fp8"`` is the control: every weight matmul's operands are rounded
to float8 e4m3 (weights per tensor, activations per row, each scaled to
the format's largest finite value) before the float32 product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.model import Spec

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(x, w, quant):
    """``x (..., K) @ w (K, N)`` in float32 (the control: fp8 operands)."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, None)
    return jnp.matmul(x, w, precision=HI)


def rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """Rotate-half RoPE: x (S, heads, hd), pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnums=(0, 1))
def layer(spec: Spec, quant, x, lw):
    """One decoder layer over a whole sequence ``x (S, d)``."""
    S = x.shape[0]
    H, KH, hd = spec.heads, spec.kv_heads, spec.head_dim
    pos = jnp.arange(S)
    h = rms(x, 1.0 + lw["ln1"], spec.eps)
    q = mm(h, lw["wq"], quant).reshape(S, H, hd)
    k = mm(h, lw["wk"], quant).reshape(S, KH, hd)
    v = mm(h, lw["wv"], quant).reshape(S, KH, hd)
    if spec.qk_norm:
        q = rms(q, 1.0 + lw["q_norm"], spec.eps)
        k = rms(k, 1.0 + lw["k_norm"], spec.eps)
    q = rope(q, pos, spec.rope_theta)
    k = rope(k, pos, spec.rope_theta)
    k = jnp.repeat(k, H // KH, axis=1)   # head h reads kv head h // (H/KH)
    v = jnp.repeat(v, H // KH, axis=1)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    o = o.reshape(S, H * hd)
    x = x + mm(o, lw["wo"], quant)
    h = rms(x, 1.0 + lw["ln2"], spec.eps)
    x = x + mm(jax.nn.silu(mm(h, lw["wg"], quant)) * mm(h, lw["wi"], quant),
               lw["w2"], quant)
    return x


@functools.partial(jax.jit, static_argnums=(0,))
def embed(spec: Spec, table, tokens):
    return table[tokens].astype(jnp.float32) * spec.d ** 0.5


@functools.partial(jax.jit, static_argnums=(0, 1))
def head(spec: Spec, quant, x, final, table):
    """Logits of the rows ``x (R, d)``: ``table`` is the stored embedding
    (tied) or the unembedding ``(d, V)``."""
    h = rms(x, 1.0 + final, spec.eps)
    if spec.tied:
        w = table.astype(jnp.float32).T * spec.d ** 0.5
    else:
        w = table
    return mm(h, w, quant)


LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "wi", "wg", "w2",
              "q_norm", "k_norm")


def hidden(spec: Spec, w: dict, tokens, quant=None):
    """Final hidden states ``(S, d)`` of ``tokens (S,)``; ``S`` a multiple
    of the query block."""
    if tokens.shape[0] % Q_BLOCK:
        raise ValueError(f"sequence length {tokens.shape[0]} is not a "
                         f"multiple of {Q_BLOCK}")
    x = embed(spec, w["embed"], tokens)
    for i in range(spec.layers):
        lw = {k: w[k][i] for k in LAYER_KEYS if k in w}
        x = layer(spec, quant, x, lw)
    return x


def logits(spec: Spec, w: dict, x_rows, quant=None):
    return head(spec, quant, x_rows, w["final_norm"],
                w["embed"] if spec.tied else w["unembed"])
