"""Attention: GQA / local(sliding-window) / MLA, chunked-flash for long prefill.

Numerics: q/k/v/o projections route through ``nmatmul`` (the paper's
configurable multiplier); the score/PV einsums stay in bf16/fp32 — the CiM
deployment model puts the approximate multipliers in the stationary-weight
arrays, while attention's activation-activation products run on the
(exact) digital datapath.  Configuration is ambient (``repro.numerics``):
the caller establishes the block's ``attn``/``cross`` scope and each
projection resolves under its own ``layer_scope`` segment
(``wq``/``wk``/``wv``/``wo``, MLA: ``wq_a``/``wq_b``/``wkv_a``/``wo``).

Memory: training/prefill attention is blockwise (online softmax over KV
chunks inside a scan over Q chunks), so the score matrix never
materializes at more than (q_chunk x kv_chunk).  Decode attends a single
query against the full cache; the cache sequence axis may be sharded over
the 'model' mesh axis (flash-decode: GSPMD turns the softmax reductions
into cross-shard collectives).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.numerics import layer_scope, nmatmul
from repro.distributed.sharding import logical_constraint

from .layers import PP, apply_rope, dense_init, rmsnorm, rmsnorm_init, softcap

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# standard GQA attention (global or sliding-window)
# ---------------------------------------------------------------------------

def gqa_init(key, cfg):
    dense = partial(dense_init, dtype=jnp.dtype(cfg.param_dtype))
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense(k1, d, H * hd, ("embed", "q_dim")),
        "wk": dense(k2, d, KH * hd, ("embed", "kv_dim")),
        "wv": dense(k3, d, KH * hd, ("embed", "kv_dim")),
        "wo": dense(k4, H * hd, d, ("q_dim", "embed")),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd)
        p["k_norm"] = rmsnorm_init(hd)
    return p


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    B, S, KH, D = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, KH, n_rep, D)).reshape(
        B, S, KH * n_rep, D
    )


def _row_pos(pos, rank):
    """Normalize a decode position to broadcast against a (..., S) score.

    ``pos`` is a scalar during lockstep decoding and a per-row ``(B,)``
    vector under continuous batching (every request sits at its own
    absolute position).  Returns an array shaped to broadcast over the
    leading batch axis of a rank-``rank`` score tensor whose last axis is
    the cache sequence."""
    pos = jnp.asarray(pos)
    if pos.ndim:
        return pos.reshape((-1,) + (1,) * (rank - 1))
    return pos


def _scatter_row(buf, new, pos):
    """Write ``new`` (B, 1, ...) into ``buf`` (B, S, ...) at per-row
    sequence position ``pos`` (B,) — the vector-position analogue of
    ``dynamic_update_slice_in_dim`` (same written bits, per-row starts)."""
    sel = jnp.arange(buf.shape[1])[None, :] == pos[:, None]
    sel = sel.reshape(sel.shape + (1,) * (buf.ndim - 2))
    return jnp.where(sel, new.astype(buf.dtype), buf)


def _cache_update(buf, new, pos):
    """Update a (B, S, ...) cache at decode position ``pos`` (scalar:
    lockstep batch; (B,) vector: continuous batching)."""
    if jnp.ndim(pos):
        return _scatter_row(buf, new, pos)
    return jax.lax.dynamic_update_slice_in_dim(
        buf, new.astype(buf.dtype), pos, axis=1)


def _mask_for(qp, kp, kvalid, causal, window):
    mask = kvalid[None, None, None, :]
    if causal:
        mask = mask & (qp[None, None, :, None] >= kp[None, None, None, :])
    if window is not None:
        mask = mask & (qp[None, None, :, None] - kp[None, None, None, :] < window)
    return mask


def blockwise_attention(q, k, v, *, causal=True, window=None, attn_cap=None,
                        q_chunk=1024, kv_chunk=1024, q_offset=0):
    """Keyword-friendly wrapper around the custom-VJP implementation.

    A traced ``q_offset`` (serving's chunked prefill jits the chunk start)
    cannot ride in ``nondiff_argnums``, so it routes directly to the
    forward impl — same bits (the custom-VJP wrapper computes its forward
    with the identical call); only training memory behaviour differs, and
    the serving path never differentiates."""
    if isinstance(q_offset, jax.Array):
        out, _ = _blockwise_fwd_impl(q, k, v, causal, window, attn_cap,
                                     q_chunk, kv_chunk, q_offset)
        return out
    return _blockwise_attention_cv(q, k, v, causal, window, attn_cap,
                                   q_chunk, kv_chunk, q_offset)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _blockwise_attention_cv(q, k, v, causal=True, window=None, attn_cap=None,
                            q_chunk=1024, kv_chunk=1024, q_offset=0):
    """Flash-style online-softmax blockwise attention with a custom VJP.

    q: (B, Sq, H, D); k/v: (B, Sk, H, D) (kv already head-repeated).
    The custom VJP is what keeps training memory flat: the forward saves
    only (q, k, v, out, lse) and the backward re-streams the score blocks
    (a plain jax.grad through the online-softmax scans would checkpoint
    every chunk of the inner loop).
    Returns (B, Sq, H, D) in fp32.
    """
    out, _ = _blockwise_fwd_impl(q, k, v, causal, window, attn_cap,
                                 q_chunk, kv_chunk, q_offset)
    return out


def _chunks(x, n, c):
    B = x.shape[0]
    return x.reshape(B, n, c, *x.shape[2:])


def _blockwise_fwd_impl(q, k, v, causal, window, attn_cap, q_chunk, kv_chunk,
                        q_offset):
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = D ** -0.5
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Sk)
    nq, nk = -(-Sq // qc), -(-Sk // kc)
    pad_q, pad_k = nq * qc - Sq, nk * kc - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    qs = _chunks(q, nq, qc).astype(jnp.bfloat16)
    ks = _chunks(k, nk, kc).astype(jnp.bfloat16)
    vs = _chunks(v, nk, kc).astype(jnp.bfloat16)
    q_pos = q_offset + jnp.arange(nq * qc).reshape(nq, qc)
    k_pos = jnp.arange(nk * kc).reshape(nk, kc)
    k_valid = k_pos < Sk

    def q_body(_, qi):
        qb, qp = qi

        def kv_body(carry, ki):
            m, l, o = carry
            kb, vb, kp, kvalid = ki
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            if attn_cap is not None:
                s = softcap(s, attn_cap)
            s = jnp.where(_mask_for(qp, kp, kvalid, causal, window), s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(jnp.bfloat16), vb,
                            preferred_element_type=jnp.float32)
            o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, H, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)
        o0 = jnp.zeros((B, qc, H, D), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            kv_body, (m0, l0, o0),
            (ks.transpose(1, 0, 2, 3, 4), vs.transpose(1, 0, 2, 3, 4),
             k_pos, k_valid))
        l = jnp.maximum(l, 1e-30)
        o = o / l.transpose(0, 2, 1)[..., None]
        lse = m + jnp.log(l)          # (B, H, qc)
        return None, (o, lse)

    _, (out, lse) = jax.lax.scan(q_body, None, (qs.transpose(1, 0, 2, 3, 4), q_pos))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nq * qc, H, D)[:, :Sq]
    lse = lse.transpose(1, 0, 3, 2).reshape(B, nq * qc, H)[:, :Sq]  # (B,Sq,H)
    return out, lse


def _blockwise_fwd(q, k, v, causal, window, attn_cap, q_chunk, kv_chunk, q_offset):
    out, lse = _blockwise_fwd_impl(q, k, v, causal, window, attn_cap,
                                   q_chunk, kv_chunk, q_offset)
    return out, (q, k, v, out, lse)


def _blockwise_bwd(causal, window, attn_cap, q_chunk, kv_chunk, q_offset,
                   res, dout):
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = D ** -0.5
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Sk)
    nq, nk = -(-Sq // qc), -(-Sk // kc)
    pad_q, pad_k = nq * qc - Sq, nk * kc - Sk
    pq = lambda x: jnp.pad(x, ((0, 0), (0, pad_q)) + ((0, 0),) * (x.ndim - 2))
    pk = lambda x: jnp.pad(x, ((0, 0), (0, pad_k)) + ((0, 0),) * (x.ndim - 2))
    if pad_q:
        q, out, dout, lse = pq(q), pq(out), pq(dout), pq(lse)
    if pad_k:
        k, v = pk(k), pk(v)
    # delta = rowsum(dout * out) per (B, Sq, H)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    qs = _chunks(q, nq, qc).astype(jnp.bfloat16)
    ks = _chunks(k, nk, kc).astype(jnp.bfloat16)
    vs = _chunks(v, nk, kc).astype(jnp.bfloat16)
    dos = _chunks(dout.astype(jnp.float32), nq, qc)
    lses = _chunks(lse, nq, qc)
    deltas = _chunks(delta, nq, qc)
    q_pos = q_offset + jnp.arange(nq * qc).reshape(nq, qc)
    k_pos = jnp.arange(nk * kc).reshape(nk, kc)
    k_valid = k_pos < Sk

    def q_body(carry, qi):
        dk_acc, dv_acc = carry  # (nk, B, kc, H, D) fp32
        qb, dob, lseb, delb, qp = qi

        def kv_body(dq_acc, ki):
            kb, vb, kp, kvalid, dk_j, dv_j = ki
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            if attn_cap is not None:
                t = jnp.tanh(s / attn_cap)
                s_capped = t * attn_cap
            else:
                s_capped = s
            mask = _mask_for(qp, kp, kvalid, causal, window)
            s_capped = jnp.where(mask, s_capped, NEG_INF)
            p = jnp.exp(s_capped - lseb.transpose(0, 2, 1)[..., None])  # (B,H,q,k)
            dv_j = dv_j + jnp.einsum("bhqk,bqhd->bkhd", p.astype(jnp.bfloat16),
                                     dob.astype(jnp.bfloat16),
                                     preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqhd,bkhd->bhqk", dob.astype(jnp.bfloat16), vb,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delb.transpose(0, 2, 1)[..., None])
            if attn_cap is not None:
                ds = ds * (1.0 - t * t)  # softcap chain rule
            ds = jnp.where(mask, ds, 0.0) * scale
            dsb = ds.astype(jnp.bfloat16)
            dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", dsb, kb,
                                         preferred_element_type=jnp.float32)
            dk_j = dk_j + jnp.einsum("bhqk,bqhd->bkhd", dsb, qb,
                                     preferred_element_type=jnp.float32)
            return dq_acc, (dk_j, dv_j)

        dq0 = jnp.zeros((B, qc, H, D), jnp.float32)
        dq, (dk_new, dv_new) = jax.lax.scan(
            kv_body, dq0,
            (ks.transpose(1, 0, 2, 3, 4), vs.transpose(1, 0, 2, 3, 4),
             k_pos, k_valid, dk_acc, dv_acc))
        return (dk_new, dv_new), dq

    dk0 = jnp.zeros((nk, B, kc, H, D), jnp.float32)
    dv0 = jnp.zeros((nk, B, kc, H, D), jnp.float32)
    (dk, dv), dq = jax.lax.scan(
        q_body, (dk0, dv0),
        (qs.transpose(1, 0, 2, 3, 4), dos.transpose(1, 0, 2, 3, 4),
         lses.transpose(1, 0, 2, 3), deltas.transpose(1, 0, 2, 3), q_pos))
    dq = dq.transpose(1, 0, 2, 3, 4).reshape(B, nq * qc, H, D)[:, :Sq]
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, nk * kc, H, D)[:, :Sk]
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, nk * kc, H, D)[:, :Sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_blockwise_attention_cv.defvjp(_blockwise_fwd, _blockwise_bwd)


def gqa_apply(params, x, cfg, spec, positions,
              cache=None, q_offset=0, causal=True, use_rope=True):
    """Returns (out, new_cache).  cache = dict(k, v) with (B, S_max, KH, D)."""
    B, S, d = x.shape
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    with layer_scope("wq"):
        q = nmatmul(x, params["wq"]).reshape(B, S, H, hd)
    with layer_scope("wk"):
        k = nmatmul(x, params["wk"]).reshape(B, S, KH, hd)
    with layer_scope("wv"):
        v = nmatmul(x, params["wv"]).reshape(B, S, KH, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    # TP region: heads sharded, sequence gathered (megatron pattern); the
    # residual stream re-shards to 'seq' at the block boundary
    q = logical_constraint(q, ("batch", None, "heads", None))
    k = logical_constraint(k, ("batch", None, "heads", None))
    v = logical_constraint(v, ("batch", None, "heads", None))
    window = spec.window if spec.attn == "local" else None

    if cache is None:
        kr = _repeat_kv(k, H // KH)
        vr = _repeat_kv(v, H // KH)
        out = blockwise_attention(
            q, kr, vr, causal=causal, window=window,
            attn_cap=cfg.attn_softcap, q_offset=q_offset,
        )
        out = logical_constraint(out, ("batch", None, "heads", None))
        new_cache = {
            "k": logical_constraint(k, ("batch", "kv_seq", None, None)),
            "v": logical_constraint(v, ("batch", "kv_seq", None, None)),
        }
    else:
        # decode (S == 1) or chunked prefill (S > 1, scalar q_offset):
        # update cache at q_offset (scalar, or (B,) vector under
        # continuous batching), attend full cache
        k_cache = _cache_update(cache["k"], k, q_offset)
        v_cache = _cache_update(cache["v"], v, q_offset)
        k_cache = logical_constraint(k_cache, ("batch", "kv_seq", None, None))
        v_cache = logical_constraint(v_cache, ("batch", "kv_seq", None, None))
        if S > 1:
            # chunked prefill: blockwise online softmax over the updated
            # cache — the same kernel the no-cache prefill path runs.
            # Cache rows from earlier chunks hold the bits a full prefill
            # would cast (bf16 store-then-read == one direct rounding)
            # and rows past the frontier mask to exact zero contributions,
            # so the chunk's outputs match the solo prefill bit-for-bit.
            out = blockwise_attention(
                q, _repeat_kv(k_cache, H // KH), _repeat_kv(v_cache, H // KH),
                causal=causal, window=window, attn_cap=cfg.attn_softcap,
                q_offset=q_offset,
            )
            out = logical_constraint(out, ("batch", None, "heads", None))
        else:
            out = decode_attention(
                q, k_cache, v_cache, q_offset, window=window,
                attn_cap=cfg.attn_softcap
            )
        new_cache = {"k": k_cache, "v": v_cache}

    out = out.astype(x.dtype).reshape(B, S, H * hd)
    with layer_scope("wo"):
        return nmatmul(out, params["wo"]).astype(x.dtype), new_cache


def decode_attention(q, k_cache, v_cache, pos, *, window=None, attn_cap=None):
    """Single-step attention against the full cache (seq may be mesh-sharded).

    ``pos`` is the absolute decode position — a scalar for a lockstep
    batch, or a ``(B,)`` vector when every row sits at its own position
    (continuous batching).

    GQA-aware: the query is grouped as (B, KH, G, D) and contracted against
    the UNexpanded cache — materializing head-repeated K/V (broadcast) makes
    GSPMD lose the cache's seq sharding and all-gather the full fp32 cache
    per layer (measured: 1 GiB x 2 x n_layers per decode step on
    qwen2-vl-72b before this formulation).
    """
    B, S1, H, D = q.shape  # S1 == 1
    KH = k_cache.shape[2]
    G = H // KH
    qr = q.reshape(B, KH, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qr.astype(jnp.bfloat16),
                   k_cache.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    if attn_cap is not None:
        s = softcap(s, attn_cap)
    k_pos = jnp.arange(k_cache.shape[1])
    pr = _row_pos(pos, 4)
    mask = k_pos[None, None, None, :] <= pr
    if window is not None:
        mask = mask & (pr - k_pos[None, None, None, :] < window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(jnp.bfloat16),
                   v_cache.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, D)


# ---------------------------------------------------------------------------
# MLA (deepseek-v3 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg):
    dense = partial(dense_init, dtype=jnp.dtype(cfg.param_dtype))
    d, H = cfg.d_model, cfg.n_heads
    m = cfg.mla
    ks = jax.random.split(key, 7)
    qd = m.nope_head_dim + m.rope_head_dim
    return {
        "wq_a": dense(ks[0], d, m.q_lora_rank, ("embed", "q_lora")),
        "q_a_norm": rmsnorm_init(m.q_lora_rank),
        "wq_b": dense(ks[1], m.q_lora_rank, H * qd, ("q_lora", "q_dim")),
        "wkv_a": dense(ks[2], d, m.kv_lora_rank + m.rope_head_dim, ("embed", "kv_lora")),
        "kv_a_norm": rmsnorm_init(m.kv_lora_rank),
        "wk_b": dense(ks[3], m.kv_lora_rank, H * m.nope_head_dim, ("kv_lora", "q_dim")),
        "wv_b": dense(ks[4], m.kv_lora_rank, H * m.v_head_dim, ("kv_lora", "q_dim")),
        "wo": dense(ks[5], H * m.v_head_dim, d, ("q_dim", "embed")),
    }


def mla_apply(params, x, cfg, spec, positions, cache=None, q_offset=0):
    """MLA with latent KV cache (the 93%-smaller cache of deepseek-v3).

    cache = dict(ckv (B,S,r), kpe (B,S,dr)).
    """
    B, S, d = x.shape
    H, m = cfg.n_heads, cfg.mla
    dn, dr, dv, r = m.nope_head_dim, m.rope_head_dim, m.v_head_dim, m.kv_lora_rank

    with layer_scope("wq_a"):
        q = nmatmul(x, params["wq_a"])
    q = rmsnorm(params["q_a_norm"], q.astype(x.dtype), cfg.norm_eps)
    with layer_scope("wq_b"):
        q = nmatmul(q, params["wq_b"]).reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)

    with layer_scope("wkv_a"):
        kv = nmatmul(x, params["wkv_a"])
    ckv, k_pe = kv[..., :r], kv[..., r:]
    ckv = rmsnorm(params["kv_a_norm"], ckv.astype(x.dtype), cfg.norm_eps)
    k_pe = apply_rope(k_pe.reshape(B, S, 1, dr), positions, cfg.rope_theta)

    wk_b = params["wk_b"].reshape(r, H, dn)
    wv_b = params["wv_b"].reshape(r, H, dv)

    if cache is None:
        # training/prefill: expand the latent into per-head k/v, blockwise attn
        q_nope = logical_constraint(q_nope, ("batch", None, "heads", None))
        k_nope = jnp.einsum("bsr,rhd->bshd", ckv, wk_b.astype(x.dtype))
        v = jnp.einsum("bsr,rhd->bshd", ckv, wv_b.astype(x.dtype))
        k_nope = logical_constraint(k_nope, ("batch", None, "heads", None))
        v = logical_constraint(v, ("batch", None, "heads", None))
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, dr))], axis=-1)
        qf = jnp.concatenate([q_nope, q_pe], axis=-1)
        # pad v head_dim up to k's for the shared kernel, then slice back
        out = blockwise_attention(qf, k, jnp.pad(v, ((0, 0),) * 3 + ((0, dn + dr - dv),)),
                                  causal=True, q_offset=q_offset)
        out = out[..., :dv]
        new_cache = {
            "ckv": logical_constraint(ckv, ("batch", "kv_seq", None)),
            "kpe": logical_constraint(k_pe.reshape(B, S, dr),
                                      ("batch", "kv_seq", None)),
        }
    elif S > 1:
        # chunked prefill: EXPANDED form over the updated latent cache.
        # The absorbed decode form below is mathematically equal but
        # bitwise different (different contraction order); re-expanding
        # the cached latent into per-head K/V reproduces the no-cache
        # prefill bits exactly, which is what keeps chunked serving
        # bit-identical to solo generation.
        ckv_c = _cache_update(cache["ckv"], ckv, q_offset)
        kpe_c = _cache_update(cache["kpe"], k_pe.reshape(B, S, dr), q_offset)
        ckv_c = logical_constraint(ckv_c, ("batch", "kv_seq", None))
        kpe_c = logical_constraint(kpe_c, ("batch", "kv_seq", None))
        Lc = ckv_c.shape[1]
        ckv_x = ckv_c.astype(x.dtype)
        q_nope = logical_constraint(q_nope, ("batch", None, "heads", None))
        k_nope = jnp.einsum("bsr,rhd->bshd", ckv_x, wk_b.astype(x.dtype))
        v = jnp.einsum("bsr,rhd->bshd", ckv_x, wv_b.astype(x.dtype))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kpe_c.astype(x.dtype)[:, :, None, :],
                                      (B, Lc, H, dr))], axis=-1)
        qf = jnp.concatenate([q_nope, q_pe], axis=-1)
        out = blockwise_attention(qf, k,
                                  jnp.pad(v, ((0, 0),) * 3 + ((0, dn + dr - dv),)),
                                  causal=True, q_offset=q_offset)
        out = out[..., :dv]
        new_cache = {"ckv": ckv_c, "kpe": kpe_c}
    else:
        # decode: absorbed form — project q into the latent space and attend
        # the latent cache directly (never materialize per-head K/V).
        ckv_c = _cache_update(cache["ckv"], ckv, q_offset)
        kpe_c = _cache_update(cache["kpe"], k_pe.reshape(B, S, dr), q_offset)
        ckv_c = logical_constraint(ckv_c, ("batch", "kv_seq", None))
        kpe_c = logical_constraint(kpe_c, ("batch", "kv_seq", None))
        q_eff = jnp.einsum("bshd,rhd->bshr", q_nope, wk_b.astype(x.dtype))  # (B,1,H,r)
        s = jnp.einsum("bhr,bkr->bhk", q_eff[:, 0].astype(jnp.bfloat16),
                       ckv_c.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bhd,bkd->bhk", q_pe[:, 0].astype(jnp.bfloat16),
                           kpe_c.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        s = s * ((dn + dr) ** -0.5)
        mask = jnp.arange(ckv_c.shape[1])[None, None, :] <= _row_pos(q_offset, 3)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhk,bkr->bhr", p.astype(jnp.bfloat16),
                           ckv_c.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        out = jnp.einsum("bhr,rhd->bhd", o_lat.astype(x.dtype), wv_b.astype(x.dtype))
        out = out.reshape(B, 1, H, dv)
        new_cache = {"ckv": ckv_c, "kpe": kpe_c}

    out = out.astype(x.dtype).reshape(B, S, H * dv)
    with layer_scope("wo"):
        return nmatmul(out, params["wo"]).astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attn_init(key, cfg):
    dense = partial(dense_init, dtype=jnp.dtype(cfg.param_dtype))
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense(k1, d, H * hd, ("embed", "q_dim")),
        "wk": dense(k2, d, H * hd, ("embed", "q_dim")),
        "wv": dense(k3, d, H * hd, ("embed", "q_dim")),
        "wo": dense(k4, H * hd, d, ("q_dim", "embed")),
    }


def cross_attn_apply(params, x, enc_out, cfg):
    B, S, d = x.shape
    Se = enc_out.shape[1]
    H, hd = cfg.n_heads, cfg.resolved_head_dim
    with layer_scope("wq"):
        q = nmatmul(x, params["wq"]).reshape(B, S, H, hd)
    with layer_scope("wk"):
        k = nmatmul(enc_out, params["wk"]).reshape(B, Se, H, hd)
    with layer_scope("wv"):
        v = nmatmul(enc_out, params["wv"]).reshape(B, Se, H, hd)
    out = blockwise_attention(q, k, v, causal=False)
    out = out.astype(x.dtype).reshape(B, S, H * hd)
    with layer_scope("wo"):
        return nmatmul(out, params["wo"]).astype(x.dtype)
