"""Mixture-of-experts with sort-based capacity dispatch (EP-shardable).

Dispatch strategy: (token, expert) assignments are sorted by expert id and
scattered into a dense ``(E, C, D)`` buffer (capacity C per expert,
overflow dropped — standard capacity-factor routing).  The buffer's expert
axis carries the ``experts`` logical axis, so under the training rules it
shards over 'model' (classic EP) and under serving rules over 'data'
(cluster-wide EP for the 671B-class models); GSPMD materializes the
all-to-alls from the sharding change at the scatter/gather boundaries.

Supports top-k routing, shared (always-on) experts (deepseek-v3), and
routes every expert matmul through the paper's numerics config — including
the routed experts: each expert's three projections resolve under the
relative ``expert{k}.{wi,wg,wo}`` paths (full paths
``blocks.{i}.mlp.expert{k}.wi`` etc.), so a per-layer policy can give
different experts different multipliers.  When every expert resolves to an
``exact`` config (the pre-policy behaviour, and any plain exact
NumericsConfig), the fused all-expert einsum datapath is kept bit-for-bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from repro.numerics import (Numerics, ambient_view, layer_scope,
                            maybe_numerics_scope, nmatmul, numerics_scope,
                            operand_tap_active, resolve)
from repro.distributed.sharding import (current_mesh_rules, logical_constraint,
                                        spec_for)

from .layers import PP, dense_init, mlp_apply, mlp_init, normal


def moe_init(key, cfg):
    d, ff = cfg.d_model, cfg.d_ff
    e = cfg.moe
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    scale = d ** -0.5
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "router": dense_init(k1, d, e.n_experts, ("embed", None), dt),
        "wi": PP(normal(k2, (e.n_experts, d, ff), scale, dt), ("experts", "embed", "mlp")),
        "wg": PP(normal(k3, (e.n_experts, d, ff), scale, dt), ("experts", "embed", "mlp")),
        "wo": PP(normal(k4, (e.n_experts, ff, d), ff ** -0.5, dt), ("experts", "mlp", "embed")),
    }
    if e.n_shared:
        p["shared"] = mlp_init(k5, d, ff * e.n_shared, dt)
    return p


def routed_expert_configs(ncfg: Numerics | None, n_experts: int) -> dict:
    """Resolved config per (projection, expert) under ``expert{k}.{name}``.

    ``ncfg`` is the block's ``mlp``-scoped policy view (or a plain config,
    which resolves identically for every expert); pass
    ``repro.numerics.ambient_view()`` to resolve from the ambient scope.
    Returns ``{name: (cfg_expert0, ..., cfg_expertE-1)}`` for wi/wg/wo.
    """
    return {name: tuple(resolve(ncfg, f"expert{k}.{name}")
                        for k in range(n_experts))
            for name in ("wi", "wg", "wo")}


def _all_exact(cfgs: dict) -> bool:
    return all(c.mode == "exact" for tup in cfgs.values() for c in tup)


def _experts_matmul(buf, w, name, out_dtype):
    """Per-expert numerics matmul: ``buf (B, E, C, D) @ w (E, D, F)``.

    Each expert's slab goes through :func:`nmatmul` under its own
    ``layer_scope`` segment (``expert{k}.{name}``), so distinct experts can
    run distinct multipliers in one forward.  Used only when some expert
    resolves non-exact (or the calibration tap is recording) — the
    all-exact fast path keeps the fused einsum.
    """
    B, E, C, D = buf.shape
    outs = []
    for k in range(E):
        with layer_scope(f"expert{k}.{name}"):
            ye = nmatmul(buf[:, k].reshape(B * C, D), w[k])
        outs.append(ye.reshape(B, C, -1).astype(out_dtype))
    return jnp.stack(outs, axis=1)


def moe_apply(params, x, cfg, ncfg: Numerics | None = None):
    """x: (B, S, D) -> (B, S, D).

    Numerics come from the ambient scope (the caller establishes this
    block's ``mlp`` prefix); the shared (always-on) expert resolves under
    the relative ``shared.*`` paths and the routed experts under
    ``expert{k}.{wi,wg,wo}``.  ``ncfg`` optionally establishes the scope
    for this call.  The router always runs exact fp32 (routing is control
    logic).  When every expert resolves to an exact config the routed slab
    multiply keeps the fused all-expert einsum in ``x.dtype`` — bit-for-bit
    the pre-policy datapath; any non-exact expert switches the layer to
    per-expert :func:`nmatmul` calls.

    Two implementations:
    * **shard_map EP** (used whenever a mesh context with a 'model' axis
      dividing E is active): textbook expert parallelism — local routing/
      sort/dispatch, one all_to_all over the expert axis, local expert
      matmuls, all_to_all back, local combine.  Per-chip dispatch traffic
      is exactly K x activation bytes; nothing is ever replicated.
      (§Perf pair 2: GSPMD's batched big-D gathers replicated the
      dispatch slab — ~200s collective term on deepseek-v3 train;
      this path removes it.)
    * **GSPMD group-local** fallback (no mesh / indivisible E): each batch
      row sorts its own S*K assignments; only int32 slot indices are
      scattered, big-D movement is gathers.
    """
    with maybe_numerics_scope(ncfg):
        state = current_mesh_rules()
        if state is not None:
            mesh, rules = state
            w_spec = spec_for(("experts", None, None), params["wi"].shape,
                              mesh, rules)
            if w_spec[0] is not None:  # experts axis actually sharded
                return _moe_apply_shardmap(params, x, cfg, mesh, rules)
        return _moe_apply_gspmd(params, x, cfg)


def _moe_apply_shardmap(params, x, cfg, mesh, rules):
    e = cfg.moe
    E, K = e.n_experts, e.top_k
    B, S, D = x.shape

    # per-expert numerics: the shard_map body traces ONCE for all EP shards,
    # so expert-heterogeneous configs cannot branch per shard — uniform
    # non-exact configs run per-local-expert nmatmul inside the body;
    # heterogeneous policies fall back to the group-local GSPMD path (which
    # slices experts at trace time and lets GSPMD partition the result).
    cfgs = routed_expert_configs(ambient_view(), E)
    if any(len(set(tup)) > 1 for tup in cfgs.values()):
        return _moe_apply_gspmd(params, x, cfg)
    ucfg = {name: tup[0] for name, tup in cfgs.items()}
    exact_experts = _all_exact(cfgs)

    x_spec = spec_for(("batch", "seq", None), x.shape, mesh, rules)
    w_spec = spec_for(("experts", None, None), params["wi"].shape, mesh, rules)
    r_spec = spec_for((None, None), params["router"].shape, mesh, rules)
    ex_axis = w_spec[0]  # mesh axis (or tuple) carrying the expert dim
    ex_axes = ex_axis if isinstance(ex_axis, tuple) else (ex_axis,)
    nm = 1
    for a in ex_axes:
        nm *= mesh.shape[a]
    # local token count per shard (static): derive from the specs
    def _shards(spec, dim_axis):
        ax = spec[dim_axis] if dim_axis < len(spec) else None
        if ax is None:
            return 1
        return int(
            __import__("numpy").prod([mesh.shape[a] for a in
                                      (ax if isinstance(ax, tuple) else (ax,))]))

    b_loc = B // _shards(x_spec, 0)
    s_loc = S // _shards(x_spec, 1)
    T_loc = b_loc * s_loc
    A = T_loc * K
    C = max(4, -(-int(T_loc * K / E * e.capacity_factor) // 4) * 4)

    def body(xl, router, wi, wg, wo):
        # xl: (b_loc, s_loc, D); wi/wg/wo: (E/nm, D, F)
        xt = xl.reshape(T_loc, D)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate, eidx = jax.lax.top_k(probs, K)
        gate = (gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)).astype(xl.dtype)

        ea = eidx.reshape(A)
        ta = jnp.arange(A, dtype=jnp.int32) // K
        order = jnp.argsort(ea)
        es, ts = ea[order], ta[order]
        counts = jnp.bincount(es, length=E)
        starts = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(A, dtype=jnp.int32) - starts[es].astype(jnp.int32)
        keep = pos < C
        pos_c = jnp.where(keep, pos, 0)
        vals = jnp.where(keep[:, None], xt[ts], 0)
        buf = jnp.zeros((E, C, D), xl.dtype).at[es, pos_c].add(vals, mode="drop")

        # EP exchange: (E, C, D) -> (E/nm, C*nm, D); local expert compute
        buf = jax.lax.all_to_all(buf, ex_axes, split_axis=0, concat_axis=1,
                                 tiled=True)
        if exact_experts:
            h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(xl.dtype))
            g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(xl.dtype))
            h = h * jax.nn.silu(g)
            out = jnp.einsum("ecf,efd->ecd", h, wo.astype(xl.dtype))
        else:
            # uniform resolved config: a nested numerics_scope locally
            # overrides the outer policy (the body cannot branch per shard)
            def local(b, w_, c_):
                with numerics_scope(c_):
                    return jnp.stack(
                        [nmatmul(b[i], w_[i]) for i in range(b.shape[0])]
                    ).astype(xl.dtype)

            h = local(buf, wi, ucfg["wi"])
            g = local(buf, wg, ucfg["wg"])
            h = h * jax.nn.silu(g)
            out = local(h, wo, ucfg["wo"])
        out = jax.lax.all_to_all(out, ex_axes, split_axis=1, concat_axis=0,
                                 tiled=True)                    # (E, C, D)

        flat = out.reshape(E * C, D)
        slot = es * C + pos_c
        picked = jnp.take(flat, jnp.where(keep, slot, 0), axis=0)
        gs = gate.reshape(A)[order]
        picked = picked * (gs * keep.astype(xl.dtype))[:, None]
        y = jnp.zeros((T_loc, D), xl.dtype).at[ts].add(picked, mode="drop")
        return y.reshape(b_loc, s_loc, D)

    y = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, r_spec, w_spec, w_spec, w_spec),
        out_specs=x_spec,
        check_rep=False,
    )(x, params["router"], params["wi"], params["wg"], params["wo"])

    if "shared" in params:
        with layer_scope("shared"):
            y = y + mlp_apply(params["shared"], x.reshape(-1, D)).astype(
                x.dtype).reshape(B, S, D)
    return y


def _moe_apply_gspmd(params, x, cfg):
    B, S, D = x.shape
    e = cfg.moe
    E, K = e.n_experts, e.top_k
    A = S * K                                        # assignments per group
    C = max(4, -(-int(S * K / E * e.capacity_factor) // 4) * 4)

    # routing (always fp32 exact — routing decisions are control logic)
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)             # (B, S, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    def route_group(eg):
        # eg: (S, K) -> int32 routing plan only (all small arrays — the big-D
        # data movement below is pure gathers, which GSPMD partitions cleanly;
        # scattering (S*K, D) values directly makes GSPMD replicate the slab)
        ea = eg.reshape(A)
        ta = jnp.arange(A, dtype=jnp.int32) // K
        order = jnp.argsort(ea)                      # local, stable
        es, ts = ea[order], ta[order]
        counts = jnp.bincount(es, length=E)
        starts = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(A, dtype=jnp.int32) - starts[es].astype(jnp.int32)
        keep = pos < C
        slot = es * C + jnp.where(keep, pos, 0)
        # src[e*C+c] = 1 + token feeding that slot (0 = empty slot)
        src = jnp.zeros((E * C,), jnp.int32).at[slot].max(
            jnp.where(keep, ts + 1, 0), mode="drop")
        # inverse: slot of each assignment (A = S*K), -1 when dropped
        inv_sorted = jnp.where(keep, slot, -1)
        inv = jnp.zeros((A,), jnp.int32).at[order].set(inv_sorted, mode="drop")
        return src, inv.reshape(S, K)

    src, inv = jax.vmap(route_group)(eidx)               # (B, E*C), (B, S, K)

    def gather_group(xg, srcg):
        vals = jnp.take(xg, jnp.maximum(srcg - 1, 0), axis=0)
        return jnp.where((srcg > 0)[:, None], vals, 0).reshape(E, C, D)

    buf = jax.vmap(gather_group)(x, src)                 # (B, E, C, D)
    buf = logical_constraint(buf, ("batch", "experts", None, None))

    # expert MLPs (weights EP-sharded over 'experts'; groups stay on 'data').
    # All-exact experts keep the fused einsum (bit-for-bit the pre-policy
    # datapath); any non-exact expert — or an active calibration tap, which
    # needs per-expert operand records — switches to per-expert nmatmul.
    cfgs = routed_expert_configs(ambient_view(), E)
    if _all_exact(cfgs) and not operand_tap_active():
        h = jnp.einsum("becd,edf->becf", buf, params["wi"].astype(x.dtype))
        g = jnp.einsum("becd,edf->becf", buf, params["wg"].astype(x.dtype))
        h = h * jax.nn.silu(g)
        out_buf = jnp.einsum("becf,efd->becd", h, params["wo"].astype(x.dtype))
    else:
        h = _experts_matmul(buf, params["wi"], "wi", x.dtype)
        g = _experts_matmul(buf, params["wg"], "wg", x.dtype)
        h = h * jax.nn.silu(g)
        out_buf = _experts_matmul(h, params["wo"], "wo", x.dtype)
    out_buf = logical_constraint(out_buf, ("batch", "experts", None, None))

    def combine_group(ob, invg, gg):
        # (E, C, D) slab -> per-token gather of its K slots, gate-weighted sum
        flat = ob.reshape(E * C, D)
        picked = jnp.take(flat, jnp.maximum(invg.reshape(-1), 0), axis=0)
        picked = jnp.where((invg.reshape(-1) >= 0)[:, None], picked, 0)
        picked = picked.reshape(S, K, D) * gg[..., None].astype(ob.dtype)
        return picked.sum(axis=1)

    y = jax.vmap(combine_group)(out_buf, inv, gate)      # (B, S, D)

    if "shared" in params:
        with layer_scope("shared"):
            y = y + mlp_apply(params["shared"], x.reshape(-1, D)).astype(
                x.dtype).reshape(B, S, D)
    return y


def aux_load_balance_loss(logits, eidx, n_experts):
    """Switch-style load-balancing auxiliary loss (framework feature)."""
    probs = jax.nn.softmax(logits, axis=-1)
    me = probs.mean(axis=0)
    one_hot = jax.nn.one_hot(eidx[..., 0], n_experts)
    fe = one_hot.mean(axis=0)
    return n_experts * jnp.sum(me * fe)
