"""Model assembly: LM decoder stacks (all 10 archs) + whisper-style enc-dec.

Depth is organized as ``segments``: ``(repeats, pattern)`` pairs scanned
with params stacked on a leading 'layers' axis (compile time flat in
depth), with configurable remat.  ``shared=True`` pattern entries reuse a
single weight set across repeats (zamba2) while still carrying
per-application caches.

Three execution modes:
  train   — no caches collected (memory-clean loss path)
  prefill — no input caches; every block *returns* its cache (SSM blocks
            compute their final state in closed form)
  decode  — single-token step against the caches

Public API:
  init(cfg, key)                          -> PP tree (use layers.unzip)
  loss_fn(params, cfg, batch)             -> scalar CE (chunked over seq)
  prefill(params, cfg, batch, max_len)    -> (last_logits, state)
  decode_step(params, cfg, batch, state, pos) -> (logits, state)
  init_state(cfg, batch, max_len)         -> serving state (abstract-init-able)
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.numerics import LayerWeight
from repro.kernels.dispatch import reads_layers_in_place
from repro.numerics import (current_numerics, expert_paths,
                            force_unroll_active, is_policy, layer_scope,
                            maybe_numerics_scope, nmatmul, numerics_scope,
                            resolve)
from repro.distributed.sharding import logical_constraint

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import (dense_init, embed_init, embed_lookup, mlp_apply,
                     mlp_init, rmsnorm, rmsnorm_init, softcap, stack_init)


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def block_init(key, cfg, spec):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if spec.kind == "ssm":
        return {"ln1": rmsnorm_init(d), "ssm": ssm_mod.ssm_init(ks[0], cfg)}
    p = {"ln1": rmsnorm_init(d), "ln2": rmsnorm_init(d)}
    if spec.attn == "mla":
        p["attn"] = attn.mla_init(ks[0], cfg)
    elif spec.attn != "none":
        p["attn"] = attn.gqa_init(ks[0], cfg)
    if cfg.encoder_layers:
        p["cross"] = attn.cross_attn_init(ks[2], cfg)
        p["ln_cross"] = rmsnorm_init(d)
    if spec.kind == "moe":
        p["mlp"] = moe_mod.moe_init(ks[1], cfg)
    else:
        p["mlp"] = mlp_init(ks[1], d, cfg.dense_ff, jnp.dtype(cfg.param_dtype))
    return p


def block_apply(params, x, cfg, spec, positions, ncfg=None, mode="train",
                cache=None, q_offset=0, causal=True, enc=None):
    """Returns (x, new_cache_or_None).

    Numerics come from the ambient scope: the caller establishes this
    block's ``blocks.{i}`` prefix (``stack_apply``) and submodules resolve
    under the relative ``attn`` / ``cross`` / ``mlp`` / ``ssm`` segments
    (see ``repro.core.policy`` for the full path table).  ``ncfg``
    optionally establishes the scope for this call (a config, or a policy
    resolved from this block down).
    """
    with maybe_numerics_scope(ncfg):
        return _block_apply(params, x, cfg, spec, positions, mode,
                            cache=cache, q_offset=q_offset, causal=causal,
                            enc=enc)


def _block_apply(params, x, cfg, spec, positions, mode, cache=None,
                 q_offset=0, causal=True, enc=None):
    if spec.kind == "ssm":
        h = rmsnorm(params["ln1"], x, cfg.norm_eps)
        with layer_scope("ssm"):
            h, new_cache = ssm_mod.ssm_apply(
                params["ssm"], h, cfg, cache=cache,
                want_state=(mode == "prefill"),
            )
        x = logical_constraint(x + h, ("batch", "seq", None))
        return x, new_cache

    new_cache = None
    if "attn" in params:
        h = rmsnorm(params["ln1"], x, cfg.norm_eps)
        with layer_scope("attn"):
            if spec.attn == "mla":
                h, new_cache = attn.mla_apply(params["attn"], h, cfg, spec,
                                              positions, cache=cache,
                                              q_offset=q_offset)
            else:
                h, new_cache = attn.gqa_apply(params["attn"], h, cfg, spec,
                                              positions, cache=cache,
                                              q_offset=q_offset, causal=causal)
        x = logical_constraint(x + h, ("batch", "seq", None))
        if mode == "train":
            new_cache = None
    if "cross" in params and enc is not None:
        h = rmsnorm(params["ln_cross"], x, cfg.norm_eps)
        with layer_scope("cross"):
            x = x + attn.cross_attn_apply(params["cross"], h, enc, cfg)
    h = rmsnorm(params["ln2"], x, cfg.norm_eps)
    with layer_scope("mlp"):
        if spec.kind == "moe":
            h = moe_mod.moe_apply(params["mlp"], h, cfg)
        else:
            h = mlp_apply(params["mlp"], h).astype(x.dtype)
    x = logical_constraint(x + h, ("batch", "seq", None))
    return x, new_cache


def block_numerics_sites(cfg, spec):
    """Relative resolution paths inside one block (every nmatmul call site
    plus the SSM scan's backend lookup) — the probe set the scan-vs-unroll
    decision in :func:`stack_apply` checks a policy against."""
    if spec.kind == "ssm":
        return ("ssm.in_proj", "ssm.out_proj", "ssm.scan")
    sites = []
    if spec.attn == "mla":
        sites += ["attn.wq_a", "attn.wq_b", "attn.wkv_a", "attn.wo"]
    elif spec.attn != "none":
        sites += ["attn.wq", "attn.wk", "attn.wv", "attn.wo"]
    if cfg.encoder_layers:
        sites += ["cross.wq", "cross.wk", "cross.wv", "cross.wo"]
    if spec.kind == "moe":
        # every routed expert resolves its three projections individually
        # (expert multiplicity: one multiplier array instance per expert)
        sites += list(expert_paths(cfg.moe.n_experts, prefix="mlp"))
        if cfg.moe.n_shared:
            sites += ["mlp.shared.wi", "mlp.shared.wg", "mlp.shared.wo"]
    else:
        sites += ["mlp.wi", "mlp.wg", "mlp.wo"]
    return tuple(sites)


def layer_paths(cfg) -> list:
    """All policy paths of the decoder stack (+ encoder + lm_head), in
    execution order — the transformer analogue of
    ``repro.models.resnet.layer_paths``, what ``sweep.auto_configure`` and
    the PPA roll-up (``sweep.policy_area`` / ``policy_ppa``) enumerate.
    MoE blocks contribute one path per routed expert projection, so expert
    multiplicity is carried by the path list itself; the scanned encoder's
    unindexed ``encoder.blocks.*`` sites each stand for
    ``cfg.encoder_layers`` physical layers — pass
    :func:`layer_path_counts` as ``counts=`` to the roll-ups to weight
    them."""
    paths = []
    idx = 0
    for repeats, pattern in cfg.segments:
        for _ in range(repeats):
            for spec in pattern:
                paths += [f"blocks.{idx}.{s}"
                          for s in block_numerics_sites(cfg, spec)]
                idx += 1
    if cfg.encoder_layers:
        enc_cfg = dataclasses.replace(cfg, encoder_layers=0)
        paths += [f"encoder.blocks.{s}"
                  for s in block_numerics_sites(enc_cfg, _enc_spec(cfg))]
    paths.append("lm_head")
    return paths


def layer_path_counts(cfg) -> dict:
    """Instance multiplicity for paths standing for >1 physical layer.

    The whisper-style encoder scans its layers with a single trace, so one
    unindexed ``encoder.blocks.{site}`` path covers ``cfg.encoder_layers``
    multiplier-array instances; every other path (decoder blocks, per-
    expert MoE projections, ``lm_head``) is already enumerated one-to-one
    by :func:`layer_paths`.  Feed this to ``sweep.policy_area`` /
    ``policy_ppa`` and ``hlo_analysis.policy_compute_scale`` as
    ``counts=``."""
    if not cfg.encoder_layers:
        return {}
    enc_cfg = dataclasses.replace(cfg, encoder_layers=0)
    return {f"encoder.blocks.{s}": cfg.encoder_layers
            for s in block_numerics_sites(enc_cfg, _enc_spec(cfg))}


def _segment_scannable(ncfg, cfg, pattern, offset, repeats):
    """True if all repeats of a segment resolve to identical numerics.

    ``jax.lax.scan`` traces its body once, so per-repeat configs can only
    differ if the segment is unrolled; this probe decides which.  Plain
    configs and single-repeat segments are trivially scannable.
    """
    if not is_policy(ncfg):
        return True
    if getattr(ncfg, "force_unroll", False):
        # sensitivity calibration: every repeat must execute eagerly so the
        # operand tap records concrete arrays (see repro.core.sensitivity)
        return False
    if repeats == 1:
        return True
    P = len(pattern)
    for pi, spec in enumerate(pattern):
        for site in block_numerics_sites(cfg, spec):
            resolved = {resolve(ncfg, f"blocks.{offset + r * P + pi}.{site}")
                        for r in range(repeats)}
            if len(resolved) > 1:
                return False
    return True


def _in_place_weights(ncfg, cfg, pattern, stacked):
    """``{pi: {path: stack}}``: the stacked 2-D matmul weights of a scanned
    segment that the segmented kernel reads in place from their stack
    (:class:`LayerWeight`), so the scan slices no per-layer copy of them.
    Empty unless every matmul runs the Pallas kernel under one config."""
    if not reads_layers_in_place(ncfg):
        return {}
    out = {}
    for pi, tree in stacked.items():
        for site in block_numerics_sites(cfg, pattern[pi]):
            path, leaf = tuple(site.split(".")), tree
            for key in path:
                leaf = leaf.get(key) if isinstance(leaf, dict) else None
            if getattr(leaf, "ndim", 0) == 3:
                out.setdefault(pi, {})[path] = leaf
    return out


def _replace_at(tree, path, value):
    """A copy of the nested dict ``tree`` with the leaf at ``path`` set to
    ``value`` (dropped if ``value`` is None)."""
    tree = dict(tree)
    if len(path) == 1:
        if value is None:
            tree.pop(path[0])
        else:
            tree[path[0]] = value
    else:
        tree[path[0]] = _replace_at(tree[path[0]], path[1:], value)
    return tree


# ---------------------------------------------------------------------------
# serving-state (cache) construction
# ---------------------------------------------------------------------------

def _block_cache(cfg, spec, batch, max_len, dtype):
    if spec.kind == "ssm":
        return ssm_mod.ssm_cache_init(cfg, batch, dtype)
    if spec.attn == "none":
        return None
    if spec.attn == "mla":
        m = cfg.mla
        return {
            "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
        }
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
    }


def init_state(cfg, batch, max_len, dtype=jnp.bfloat16):
    """Serving state: per-block caches (stacked over repeats) + enc_out slot."""
    layers = []
    for repeats, pattern in cfg.segments:
        seg = {}
        for pi, spec in enumerate(pattern):
            c = _block_cache(cfg, spec, batch, max_len, dtype)
            if c is not None:
                seg[pi] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (repeats,) + a.shape), c
                )
        layers.append(seg)
    state = {"layers": layers}
    if cfg.encoder_layers:
        state["enc_out"] = jnp.zeros((batch, cfg.enc_len, cfg.d_model), dtype)
    return state


def _merge_block_cache(spec, empty, run):
    """Write prefill-produced cache (length S) into the max_len buffer."""
    if spec.kind == "ssm":
        return jax.tree.map(lambda e, r: r.astype(e.dtype), empty, run)

    def write(buf, new, taxis):
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), 0, axis=taxis
        )

    out = {}
    for k in empty:
        taxis = empty[k].ndim - (3 if k in ("k", "v") else 2)
        out[k] = write(empty[k], run[k], taxis)
    return out


# ---------------------------------------------------------------------------
# decoder stack
# ---------------------------------------------------------------------------

def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable if cfg.remat == "full"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy)


def stack_params_init(cfg, key):
    params = {}
    nseg = sum(len(p) for _, p in cfg.segments)
    keys = jax.random.split(key, nseg + 1)
    ki = 0
    for si, (repeats, pattern) in enumerate(cfg.segments):
        for pi, spec in enumerate(pattern):
            k = keys[ki]; ki += 1
            if spec.shared:
                params[f"seg{si}_p{pi}"] = block_init(k, cfg, spec)
            else:
                params[f"seg{si}_p{pi}"] = stack_init(
                    partial(block_init, cfg=cfg, spec=spec), k, repeats
                )
    return params


def stack_apply(params, x, cfg, ncfg=None, positions=None, mode="train",
                caches=None, q_offset=0, causal=True, enc=None):
    """Run all segments.  Returns (x, new_caches list-of-dicts or None).

    Numerics come from the ambient scope (a NumericsConfig — one global
    setting — or a NumericsPolicy): block ``(r, pi)`` of segment ``si``
    resolves under the ``blocks.{global_layer_index}`` layer scope.
    Scanned segments whose repeats resolve to different configs are
    transparently unrolled (each repeat traces its own numerics); segments
    uniform under the policy keep the compile-time-flat scan.  ``ncfg``
    optionally establishes the scope for this call.
    """
    with maybe_numerics_scope(ncfg):
        return _stack_apply(params, x, cfg, positions, mode, caches=caches,
                            q_offset=q_offset, causal=causal, enc=enc)


def _stack_apply(params, x, cfg, positions, mode, caches=None,
                 q_offset=0, causal=True, enc=None):
    ncfg = current_numerics()
    collect = mode != "train"
    new_caches = []
    layer_offset = 0
    for si, (repeats, pattern) in enumerate(cfg.segments):
        P = len(pattern)
        seg_caches = caches[si] if caches is not None else {}
        stacked = {pi: params[f"seg{si}_p{pi}"]
                   for pi, spec in enumerate(pattern) if not spec.shared}
        shared = {pi: params[f"seg{si}_p{pi}"]
                  for pi, spec in enumerate(pattern) if spec.shared}

        def seg_body_at(base, x, layer_params, layer_caches,
                        _pattern=pattern, _shared=shared):
            out_caches = {}
            for pi, spec in enumerate(_pattern):
                p = _shared[pi] if spec.shared else layer_params[pi]
                c = layer_caches.get(pi)
                with layer_scope(f"blocks.{base + pi}"):
                    x, nc = _block_apply(p, x, cfg, spec, positions, mode,
                                         cache=c, q_offset=q_offset,
                                         causal=causal, enc=enc)
                if nc is not None and collect:
                    out_caches[pi] = nc
            return x, out_caches

        take_r = lambda tree, r: jax.tree.map(lambda a: a[r], tree)
        if _segment_scannable(ncfg, cfg, pattern, layer_offset, repeats):
            # uniform numerics across repeats: scan (paths resolve with the
            # segment's first global index — valid exactly because uniform).
            # Weights the kernel reads in place stay out of the scanned
            # inputs; the body hands them over as layer r of their stack.
            in_place = _in_place_weights(ncfg, cfg, pattern, stacked)
            sliced = dict(stacked)
            for pi, stacks in in_place.items():
                for path in stacks:
                    sliced[pi] = _replace_at(sliced[pi], path, None)

            def seg_body(x, xs, _base=layer_offset, _in_place=in_place):
                layer_params, layer_caches, r = xs
                layer_params = dict(layer_params)
                for pi, stacks in _in_place.items():
                    for path, stack in stacks.items():
                        layer_params[pi] = _replace_at(
                            layer_params[pi], path, LayerWeight(stack, r))
                return seg_body_at(_base, x, layer_params, layer_caches)

            body = _remat(seg_body, cfg)
            layer_ids = jnp.arange(repeats, dtype=jnp.int32)
            if repeats == 1:
                x, outc = body(x, ({pi: take_r(v, 0) for pi, v in sliced.items()},
                                   {pi: take_r(v, 0) for pi, v in seg_caches.items()},
                                   layer_ids[0]))
                outc = {pi: jax.tree.map(lambda a: a[None], v)
                        for pi, v in outc.items()}
            else:
                x, outc = jax.lax.scan(body, x, (sliced, seg_caches, layer_ids))
        else:
            # heterogeneous policy: unroll so each repeat traces its own
            # numerics; caches re-stack to the scanned layout (leading
            # repeats axis) so prefill/decode consumers see one format.
            # A force_unroll (calibration) policy additionally skips remat —
            # jax.checkpoint traces its body, which would hide operands from
            # the sensitivity tap.
            wrap = ((lambda f: f) if force_unroll_active()
                    else (lambda f: _remat(f, cfg)))
            per_repeat = []
            for r in range(repeats):
                def one_repeat(x, xs, _base=layer_offset + r * P):
                    return seg_body_at(_base, x, xs[0], xs[1])

                x, oc = wrap(one_repeat)(
                    x, ({pi: take_r(v, r) for pi, v in stacked.items()},
                        {pi: take_r(v, r) for pi, v in seg_caches.items()}))
                per_repeat.append(oc)
            outc = {pi: jax.tree.map(lambda *a: jnp.stack(a),
                                     *[oc[pi] for oc in per_repeat])
                    for pi in (per_repeat[0] if per_repeat else {})}
        new_caches.append(outc if collect else {})
        layer_offset += repeats * P
    return x, (new_caches if collect else None)


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def init(cfg, key):
    """Seeded parameters: every weight of two or more dims is created
    directly in ``cfg.param_dtype`` (no float32 copy on the way); norm
    scales and other vectors stay float32."""
    k_emb, k_stack, k_head, k_enc = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    params = {
        "embed": embed_init(k_emb, cfg.vocab, cfg.d_model, dtype=dt),
        "final_norm": rmsnorm_init(cfg.d_model),
        **stack_params_init(cfg, k_stack),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(k_head, cfg.d_model, cfg.vocab,
                                       ("embed_table", "vocab"), dt)
    if cfg.encoder_layers:
        params["encoder"] = encoder_init(cfg, k_enc)
    return params


def _positions_for(cfg, batch, B, S, offset=0):
    if "positions" in batch:
        return batch["positions"]
    off = jnp.asarray(offset, jnp.int32)
    if off.ndim:
        # per-row decode offsets (continuous batching): each request sits
        # at its own absolute position
        off = off[:, None]
    pos = jnp.arange(S, dtype=jnp.int32)[None, :] + off
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[..., None], (B, S, 3))
    return pos


def _embed_inputs(params, cfg, batch):
    if "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_lookup(params["embed"], batch["tokens"]).astype(jnp.dtype(cfg.dtype))
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return logical_constraint(x, ("batch", "seq", None))


def backbone(params, cfg, batch, mode, caches=None, q_offset=0, enc=None):
    """Embeds -> (encoder) -> decoder stack -> final norm.

    Establishes the numerics scope from ``cfg.numerics`` — everything
    below resolves ambiently (``repro.numerics``)."""
    with numerics_scope(cfg.numerics):
        x = _embed_inputs(params, cfg, batch)
        B, S = x.shape[:2]
        positions = _positions_for(cfg, batch, B, S, offset=q_offset)
        if cfg.encoder_layers and enc is None:
            enc = encoder_apply(params["encoder"], cfg, batch)
        x, new_caches = _stack_apply(params, x, cfg, positions, mode,
                                     caches=caches, q_offset=q_offset, enc=enc)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x, new_caches, enc


def logits_fn(params, cfg, hidden):
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    if is_policy(cfg.numerics):
        # the unembedding participates in per-layer policies as ``lm_head``
        # (a policy default of exact/bf16 reproduces the legacy head)
        with numerics_scope(cfg.numerics), layer_scope("lm_head"):
            logits = nmatmul(hidden, w)
    else:
        logits = jax.lax.dot_general(
            hidden.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            (((hidden.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    if cfg.tie_embeddings:
        # the tied table has unit-variance rows (embed_init scale=1.0), so
        # match the untied head's d**-0.5 init: logits start at unit scale
        # instead of sqrt(d_model) (which stalls early training)
        logits = logits * (cfg.d_model ** -0.5)
    logits = logical_constraint(logits, ("batch", "seq", "vocab"))
    return softcap(logits, cfg.logit_softcap)


def loss_fn(params, cfg, batch, batch_chunks: int | None = None):
    """Causal-LM cross-entropy, chunked over the BATCH dim.

    Chunking over batch (not sequence) preserves the activations' sharding
    under GSPMD — a (B,S,·)->(B,nc,c,·) sequence reshape would break the
    'seq' sharding and replicate fp32 logits on every chip.  Each chunk is
    rematerialized so the backward pass recomputes its logits instead of
    checkpointing (B_c, S, V).
    """
    hidden, _, _ = backbone(params, cfg, batch, mode="train")
    targets = batch["targets"]
    B, S = targets.shape
    if batch_chunks is None:
        batch_chunks = cfg.loss_batch_chunks
    nb = batch_chunks if B % batch_chunks == 0 else 1
    hid = hidden.reshape(nb, B // nb, S, hidden.shape[-1])
    tgt = targets.reshape(nb, B // nb, S)

    def chunk_loss(carry, xs):
        h, t = xs
        lg = logits_fn(params, cfg, h)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, jnp.maximum(t, 0)[..., None], axis=-1)[..., 0]
        valid = (t >= 0).astype(jnp.float32)
        nll = (lse - gold) * valid
        loss, count = carry
        return (loss + nll.sum(), count + valid.sum()), None

    body = jax.checkpoint(chunk_loss, policy=jax.checkpoint_policies.nothing_saveable)
    (tot, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (hid, tgt))
    return tot / jnp.maximum(cnt, 1.0)


def prefill(params, cfg, batch, max_len=None):
    """Process the prompt; returns (last-token logits, serving state)."""
    ref = batch["tokens"] if "tokens" in batch else batch["embeds"]
    B, S = ref.shape[0], ref.shape[1]
    max_len = max_len or S
    hidden, run_caches, enc = backbone(params, cfg, batch, mode="prefill")
    state = init_state(cfg, B, max_len, dtype=jnp.dtype(cfg.dtype))
    merged = []
    for (repeats, pattern), empty_seg, run_seg in zip(cfg.segments,
                                                      state["layers"], run_caches):
        seg = {}
        for pi in empty_seg:
            seg[pi] = _merge_block_cache(pattern[pi], empty_seg[pi], run_seg[pi])
        merged.append(seg)
    state["layers"] = merged
    if enc is not None:
        state["enc_out"] = enc.astype(jnp.dtype(cfg.dtype))
    return logits_fn(params, cfg, hidden[:, -1:]), state


def decode_step(params, cfg, batch, state, pos):
    """One decode step: batch['token'] (B,1) int32; pos = absolute position.

    ``pos`` is a scalar when the whole batch decodes in lockstep
    (``Session.generate``) or a ``(B,)`` int32 vector when each row sits at
    its own position (the continuous-batching engine of
    ``repro.serving``); rope, cache writes and attention masks all follow
    the per-row positions."""
    enc = state.get("enc_out")
    hidden, new_layers, _ = backbone(
        params, cfg, {"tokens": batch["token"]},
        mode="decode", caches=state["layers"], q_offset=pos, enc=enc,
    )
    new_state = dict(state)
    new_state["layers"] = new_layers
    return logits_fn(params, cfg, hidden), new_state


# ---------------------------------------------------------------------------
# whisper-style encoder
# ---------------------------------------------------------------------------

def _enc_spec(cfg):
    return dataclasses.replace(cfg.segments[0][1][0], kind="dense", attn="global")


def encoder_init(cfg, key):
    spec = _enc_spec(cfg)
    enc_cfg = dataclasses.replace(cfg, encoder_layers=0)  # no cross in encoder
    ks = jax.random.split(key, 2)
    return {
        "blocks": stack_init(partial(block_init, cfg=enc_cfg, spec=spec),
                             ks[0], cfg.encoder_layers),
        "norm": rmsnorm_init(cfg.d_model),
    }


def encoder_apply(params, cfg, batch, ncfg=None):
    with maybe_numerics_scope(ncfg):
        x = batch["enc_embeds"].astype(jnp.dtype(cfg.dtype))
        x = logical_constraint(x, ("batch", "seq", None))
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        spec = _enc_spec(cfg)

        def body(x, layer_params):
            # encoder layers scan with one trace, so rules cannot
            # distinguish them: all resolve under the unindexed
            # ``encoder.blocks`` prefix
            with layer_scope("encoder.blocks"):
                x, _ = _block_apply(layer_params, x, cfg, spec, positions,
                                    mode="train", causal=False)
            return x, {}

        if force_unroll_active():
            # sensitivity calibration: the scan traces its body once, so
            # the operand tap would never see concrete encoder operands —
            # run each layer eagerly instead (no remat either: checkpoint
            # also traces).  Paths stay the unindexed ``encoder.blocks.*``
            # (matching policy resolution), so the tap records one sample
            # per site with ``calls == cfg.encoder_layers``.
            for r in range(cfg.encoder_layers):
                x, _ = body(x, jax.tree.map(lambda a: a[r], params["blocks"]))
        else:
            x, _ = jax.lax.scan(_remat(body, cfg), x, params["blocks"])
        return rmsnorm(params["norm"], x, cfg.norm_eps)
