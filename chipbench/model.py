"""Configurations and seeded weights.

A configuration file holds the published ``config.json`` keys of a dense
SwiGLU/GQA decoder (plus ``qk_norm``); :func:`arch_config` turns it into
the program's ``ArchConfig``.  :func:`make_weights` draws every weight
from the seed on the device in one jitted call, in the dtype it is served
in, in a neutral layout that both the program (:func:`program_params`)
and the float32 reference (``chipbench/reference.py``) read.

Weight semantics.  Matrices are ``N(0, initializer_range)`` as the
published init draws them.  The program scales the input embedding by
``sqrt(hidden_size)`` and keeps RMSNorm weights as ``1 + scale``, so the
stored table is the published one divided by ``sqrt(hidden_size)`` and the
stored norm value is the published weight minus one; the reference
multiplies and adds them back, in float32.
"""
from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent
NORM_SPREAD = 0.1   # norm weights are 1 + NORM_SPREAD * N(0, 1)


class Spec:
    """The sizes of one configuration, read from its file."""

    def __init__(self, hf: dict, name: str):
        for key, want in (("hidden_act", "silu"),
                          ("attention_bias", False),
                          ("sliding_window", None)):
            if hf.get(key, want) != want:
                raise ValueError(f"{name}: {key}={hf[key]!r} is not a dense "
                                 f"SwiGLU/GQA decoder this benchmark runs")
        self.name = name
        self.hf = hf
        self.d = int(hf["hidden_size"])
        self.ff = int(hf["intermediate_size"])
        self.layers = int(hf["num_hidden_layers"])
        self.heads = int(hf["num_attention_heads"])
        self.kv_heads = int(hf["num_key_value_heads"])
        self.head_dim = int(hf.get("head_dim") or self.d // self.heads)
        self.vocab = int(hf["vocab_size"])
        self.tied = bool(hf["tie_word_embeddings"])
        self.qk_norm = bool(hf.get("qk_norm", False))
        self.rope_theta = float(hf["rope_theta"])
        self.eps = float(hf["rms_norm_eps"])
        self.init_std = float(hf.get("initializer_range", 0.02))
        self.dtype = jnp.dtype(hf.get("torch_dtype", "bfloat16"))

    # a Spec is a static jit argument
    def _key(self):
        return (self.name, json.dumps(self.hf, sort_keys=True))

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Spec) and self._key() == other._key()

    def leaf_shapes(self) -> dict:
        """Neutral layout: name -> (shape, dtype)."""
        d, L, hd = self.d, self.layers, self.head_dim
        q, kv = self.heads * hd, self.kv_heads * hd
        w, f32 = self.dtype, jnp.dtype(jnp.float32)
        shapes = {
            "embed": ((self.vocab, d), w),
            "final_norm": ((d,), f32),
            "ln1": ((L, d), f32),
            "ln2": ((L, d), f32),
            "wq": ((L, d, q), w),
            "wk": ((L, d, kv), w),
            "wv": ((L, d, kv), w),
            "wo": ((L, q, d), w),
            "wi": ((L, d, self.ff), w),
            "wg": ((L, d, self.ff), w),
            "w2": ((L, self.ff, d), w),
        }
        if self.qk_norm:
            shapes["q_norm"] = ((L, hd), f32)
            shapes["k_norm"] = ((L, hd), f32)
        if not self.tied:
            shapes["unembed"] = ((d, self.vocab), w)
        return shapes


def load_spec(name: str, hf: dict | None = None) -> Spec:
    """The configuration ``configs/<name>.json`` (or ``hf`` in its place)."""
    if hf is None:
        with open(ROOT / "configs" / f"{name}.json") as f:
            hf = json.load(f)
    return Spec(hf, name)


def arch_config(spec: Spec):
    """The program's ``ArchConfig`` for ``spec``: every layer dense with
    global attention, weights and activations in the served dtype."""
    from repro.configs.base import ArchConfig, LayerSpec

    return ArchConfig(
        arch_id=spec.name, family="dense", d_model=spec.d,
        n_heads=spec.heads, n_kv_heads=spec.kv_heads,
        head_dim=spec.head_dim, d_ff=spec.ff, vocab=spec.vocab,
        segments=((spec.layers, (LayerSpec(kind="dense", attn="global"),)),),
        qk_norm=spec.qk_norm, rope_theta=spec.rope_theta,
        tie_embeddings=spec.tied, norm_eps=spec.eps,
        dtype=spec.dtype.name, param_dtype=spec.dtype.name)


def seed_words(seed: int):
    """``--seed`` (any non-negative integer up to 2**63) as two uint32
    words, so that one compiled generator serves every seed."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 63:
        raise ValueError(f"seed {seed} is outside [0, 2**63)")
    return (jnp.asarray(seed & 0xFFFFFFFF, jnp.uint32),
            jnp.asarray(seed >> 32, jnp.uint32))


@functools.partial(jax.jit, static_argnums=0)
def _make_weights(spec: Spec, lo, hi):
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    out = {}
    for i, (name, (shape, dtype)) in enumerate(sorted(spec.leaf_shapes().items())):
        k = jax.random.fold_in(key, i)
        if name in ("final_norm", "ln1", "ln2", "q_norm", "k_norm"):
            std = NORM_SPREAD
        elif name == "embed":
            std = spec.init_std / spec.d ** 0.5
        else:
            std = spec.init_std

        def draw(kk, shp=shape, std=std, dtype=dtype):
            return (jax.random.normal(kk, shp, jnp.float32) * std).astype(dtype)

        if len(shape) == 3:
            # one layer at a time: the float32 draw never exceeds a layer
            out[name] = jax.lax.map(
                lambda kk, shp=shape[1:]: draw(kk, shp),
                jax.random.split(k, shape[0]))
        else:
            out[name] = draw(k)
    return out


def make_weights(spec: Spec, seed: int) -> dict:
    """Every weight of ``spec`` drawn from ``seed``, on the device, in one
    jitted call (neutral layout, see :meth:`Spec.leaf_shapes`)."""
    return _make_weights(spec, *seed_words(seed))


def program_params(spec: Spec, w: dict) -> dict:
    """The neutral layout as the program's parameter tree
    (``repro.models.transformer.init``'s structure, one scanned segment)."""
    attn = {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]}
    if spec.qk_norm:
        attn["q_norm"] = {"scale": w["q_norm"]}
        attn["k_norm"] = {"scale": w["k_norm"]}
    params = {
        "embed": w["embed"],
        "final_norm": {"scale": w["final_norm"]},
        "seg0_p0": {
            "ln1": {"scale": w["ln1"]},
            "ln2": {"scale": w["ln2"]},
            "attn": attn,
            "mlp": {"wi": w["wi"], "wg": w["wg"], "wo": w["w2"]},
        },
    }
    if not spec.tied:
        params["unembed"] = w["unembed"]
    return params


def weight_bytes(spec: Spec) -> int:
    return sum(math.prod(s) * d.itemsize
               for s, d in spec.leaf_shapes().values())
