"""Shared building blocks: params-with-logical-axes, norms, embeddings, RoPE, MLPs.

Parameters are created as ``PP(value, axes)`` leaves — ``axes`` is a tuple
of *logical* axis names (one per array dim) that
``repro.distributed.sharding`` later maps onto mesh axes.  ``unzip``
separates a PP-tree into (params, specs); all model ``apply`` functions
take the plain params tree.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.numerics import (Numerics, layer_scope, maybe_numerics_scope,
                            nmatmul)


class PP:
    """A parameter leaf: array value + logical axis names.

    Registered as a pytree node with ``axes`` as static aux data, so PP
    trees flow through ``jax.vmap`` / ``jax.eval_shape`` (abstract init for
    the dry-run) while ``unzip`` can still split values from specs.
    """

    __slots__ = ("value", "axes")

    def __init__(self, value, axes):
        self.value = value
        self.axes = tuple(axes)

    def __repr__(self):
        shape = getattr(self.value, "shape", None)
        return f"PP{tuple(shape) if shape is not None else '?'}:{self.axes}"


jax.tree_util.register_pytree_node(
    PP, lambda p: ((p.value,), p.axes), lambda axes, ch: PP(ch[0], axes)
)


def _is_pp(x):
    return isinstance(x, PP)


def unzip(tree):
    """PP-tree -> (params tree of arrays, specs tree of logical-axes tuples)."""
    params = jax.tree.map(lambda p: p.value, tree, is_leaf=_is_pp)
    specs = jax.tree.map(lambda p: p.axes, tree, is_leaf=_is_pp)
    return params, specs


def normal(key, shape, scale, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


def dense_init(key, d_in, d_out, axes, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    return PP(normal(key, (d_in, d_out), scale, dtype), axes)


def stack_init(init_fn: Callable, key, repeats: int):
    """vmap an init over a leading 'layers' axis; prepends 'layers' to specs."""
    keys = jax.random.split(key, repeats)
    tree = jax.vmap(init_fn)(keys)
    return jax.tree.map(
        lambda p: PP(p.value, ("layers",) + p.axes), tree, is_leaf=_is_pp
    )


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d, name="scale"):
    return {name: PP(jnp.zeros((d,), jnp.float32), ("embed",))}


def rmsnorm(params, x, eps=1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab, d, scale=1.0, dtype=jnp.float32):
    # vocab-sharded ONLY ('embed_table' never joins the fsdp rule): a 2D-
    # sharded table makes GSPMD all-gather it around the token gather.
    return PP(normal(key, (vocab, d), scale, dtype), ("vocab", "embed_table"))


def embed_lookup(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed(x, table, ncfg: Numerics | None = None, transpose=True,
            name: str = "lm_head"):
    """Unembedding matmul under the ambient numerics scope.

    Resolves under the ``lm_head`` layer path (override via ``name``), so
    the site participates in per-layer policies and the sensitivity tap
    like every other projection; ``ncfg`` optionally establishes the scope
    for this call.
    """
    w = table.T if transpose else table
    with maybe_numerics_scope(ncfg), layer_scope(name):
        return nmatmul(x, w)


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE sections)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim, theta=10000.0):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta=10000.0, sections=None):
    """x: (..., S, H, D); positions: (..., S) or (..., S, 3) for M-RoPE."""
    D = x.shape[-1]
    half = D // 2
    freqs = rope_freqs(D, theta)  # (half,)
    if sections is None:
        ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,half)
    else:
        # M-RoPE: frequency bands split into (t, h, w) sections, each using
        # its own position stream (qwen2-vl §2; text positions are identical
        # across sections, so this reduces to standard RoPE for pure text)
        st, sh, sw = sections
        assert st + sh + sw == half, (sections, half)
        sec = jnp.concatenate([
            jnp.zeros((st,), jnp.int32),
            jnp.ones((sh,), jnp.int32),
            jnp.full((sw,), 2, jnp.int32),
        ])  # (half,) -> which position stream drives each band
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),  # (..., S, 3)
            jnp.broadcast_to(sec, positions.shape[:-1] + (half,)).astype(jnp.int32),
            axis=-1,
        )  # (..., S, half)
        ang = pos[..., :, None, :] * freqs  # (..., S, 1, half)
    cos = jnp.cos(ang).astype(x.dtype)
    sin = jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d, ff, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": dense_init(k1, d, ff, ("embed", "mlp"), dtype),
        "wg": dense_init(k2, d, ff, ("embed", "mlp"), dtype),
        "wo": dense_init(k3, ff, d, ("mlp", "embed"), dtype),
    }


def mlp_apply(params, x, ncfg: Numerics | None = None):
    """Gated MLP under the ambient numerics scope (relative call-site
    paths ``wi``/``wg``/``wo``); ``ncfg`` optionally establishes the scope
    for this call (a config, or a policy resolved from here down)."""
    from repro.distributed.sharding import logical_constraint

    hidden_axes = ("batch",) + (None,) * (x.ndim - 2) + ("mlp",)
    with maybe_numerics_scope(ncfg):
        with layer_scope("wi"):
            h = nmatmul(x, params["wi"])
        with layer_scope("wg"):
            g = nmatmul(x, params["wg"])
        h = logical_constraint(h, hidden_axes)
        g = logical_constraint(g, hidden_axes)
        h = h * jax.nn.silu(g)
        with layer_scope("wo"):
            return nmatmul(h.astype(x.dtype), params["wo"])


def softcap(x, cap):
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap
