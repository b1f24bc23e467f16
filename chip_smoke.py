"""Smoke run of the serving main path on one TPU chip at qwen3-4b's
published widths (d_model 2560, 36 layers, 32/8 heads, head_dim 128,
d_ff 9728, vocab 151936), with seeded bf16 weights.

    python chip_smoke.py

Everything runs in this one process, which holds the chip.  Phases, in
order; any failed check raises and the script exits non-zero:

1. device  -- JAX's first device must be a TPU; there is no CPU branch.
2. kernels -- the segmented-matmul Pallas kernel (``dispatch.matmul``,
   ``backend="pallas"``) against its XLA reference
   (``ref.afpm_matmul_ref``) at the decode, prefill-chunk, ragged-chunk
   and tied-unembed shapes, passes 1 and 3, within the float32
   accumulation-order bound stated in :func:`check_kernels`.
3. session -- ``Session("qwen3-4b", reduced=False)``: every weight of two
   or more dims in bf16, parameter bytes and ``memory_stats()`` printed.
4. tiers   -- the decode step of each default tier, lowered: premium
   (``exact``) holds no Pallas call, standard (``segmented3``) and bulk
   (``segmented1``) hold ``tpu_custom_call``; one compiled step's logits
   are finite.
5. serve   -- 12 requests (4 per tier, seeded prompt lengths 8..200,
   16 new tokens each) through ``Session.serving_engine(DEFAULT_TIERS,
   slots=4, max_len=256, prefill_chunk=32)``: every request finishes
   with 16 in-vocab tokens, and the longest prompt of each tier yields
   the tokens ``Session.generate`` gives for it under that tier.

Timings printed on the way are informational, not benchmark numbers.
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
``tests/test_chip_smoke.py`` rehearses phases 2-5 on the CPU at the
reduced config.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen3-4b"
SEED = 0
SLOTS, MAX_LEN, PREFILL_CHUNK, NEW_TOKENS = 4, 256, 32, 16
REQUESTS_PER_TIER = 4
PROMPT_LENS = (8, 200)       # inclusive range of seeded prompt lengths
F32_EPS = 2.0 ** -24         # unit roundoff of float32


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums JAX's backend-compile durations (informational)."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.seconds += secs
            self.count += 1


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# -- phase 2: kernels --------------------------------------------------------

def kernel_cases(cfg):
    """(name, x shape, w shape) at ``cfg``'s widths: the decode q and down
    projections, a full and a ragged prefill chunk, the tied unembed."""
    d, q = cfg.d_model, cfg.n_heads * cfg.resolved_head_dim
    return (
        ("decode_q", (SLOTS, 1, d), (d, q)),
        ("decode_down", (SLOTS, 1, cfg.d_ff), (cfg.d_ff, d)),
        ("prefill_chunk", (1, PREFILL_CHUNK, d), (d, q)),
        ("ragged_chunk", (1, 7, d), (d, q)),
        ("tied_unembed", (SLOTS, 1, d), (d, cfg.vocab)),
    )


@functools.partial(jax.jit, static_argnums=(2, 3))
def _kernel_vs_ref(x, w, passes: int, backend: str):
    from repro.kernels import dispatch, ref

    got = dispatch.matmul(x, w, passes, backend=backend)
    want = ref.afpm_matmul_ref(x, w, passes)
    check(got.shape == want.shape,
          f"kernel output {got.shape}, reference {want.shape}")
    other = ref.afpm_matmul_ref(x, w, 4 - passes)  # 1 <-> 3
    mag = jnp.dot(jnp.abs(x), jnp.abs(w),
                  precision=jax.lax.Precision.HIGHEST)
    K = x.shape[-1]
    # The products of bf16 segments are exact in float32, so kernel and
    # reference sum the same <= 3K exact products, only in a different
    # order.  Each sum is within (3K - 1) * eps * sum|p| of the exact one,
    # and sum|p| <= 1.02 * sum|x||w| (segments exceed |x| by <= 2^-8).
    bound = 2 * 3 * K * F32_EPS * 1.02 * mag
    err = jnp.abs(got - want)
    return jnp.max(err), jnp.max(err - bound), jnp.max(jnp.abs(want - other))


def check_kernels(cfg, backend: str = "pallas"):
    """Kernel vs reference at every case of :func:`kernel_cases`, passes 1
    and 3, float32 operands (so the low segments are non-zero).  Requires
    every element within the accumulation-order bound, and the largest
    error under an eighth of the gap to the other pass level."""
    key = jax.random.PRNGKey(SEED)
    for name, xs, ws in kernel_cases(cfg):
        key, kx, kw = jax.random.split(key, 3)
        x = jax.random.normal(kx, xs, jnp.float32)
        w = jax.random.normal(kw, ws, jnp.float32)
        for passes in (1, 3):
            err, over, gap = jax.device_get(
                _kernel_vs_ref(x, w, passes, backend))
            print(f"[kernels] {name} {xs}@{ws} passes={passes}: "
                  f"max|kernel-ref|={err:.3e}, gap to passes="
                  f"{4 - passes} {gap:.3e}", flush=True)
            check(over <= 0, f"{name} passes={passes}: kernel exceeds the "
                             f"accumulation-order bound by {over:.3e}")
            check(err < gap / 8, f"{name} passes={passes}: error {err:.3e} "
                                 f"does not separate pass levels ({gap:.3e})")
        del x, w


# -- phase 3: session --------------------------------------------------------

def build_session(reduced: bool = False):
    """The qwen3-4b Session with its seeded weights materialised on the
    device."""
    from repro.session import Session

    t0 = time.perf_counter()
    sess = Session(ARCH, reduced=reduced, seed=SEED)
    leaves = jax.tree_util.tree_flatten_with_path(
        jax.block_until_ready(sess.params))[0]
    dt = time.perf_counter() - t0
    want = jnp.dtype(sess.config.param_dtype)
    # every weight in the config's param_dtype; norm scales may be float32
    wrong = sorted(jax.tree_util.keystr(k) for k, a in leaves
                   if a.dtype != want and k[-1].key != "scale")
    check(not wrong, f"not stored as {want}: {wrong}")
    n = sum(a.size for _, a in leaves)
    nbytes = sum(a.nbytes for _, a in leaves)
    print(f"[session] {ARCH} d_model={sess.config.d_model} "
          f"layers={sess.config.n_layers} vocab={sess.config.vocab}: "
          f"{n:,} parameters, {nbytes:,} bytes (weights {want}) in "
          f"{dt:.1f}s", flush=True)
    print(f"[session] memory_stats: {jax.devices()[0].memory_stats()}",
          flush=True)
    return sess


# -- phase 4: tiers ----------------------------------------------------------

def check_tiers(sess, tiers):
    """Lower (and run once) each tier's decode step over ``SLOTS`` rows
    and ``MAX_LEN`` positions; returns ``{tier: True if its lowered
    program holds a Pallas TPU call}``."""
    from repro.models import transformer

    has_kernel = {}
    rng = np.random.default_rng(SEED)
    for spec in tiers:
        cfg = sess.replace(policy=spec.policy).config
        step = jax.jit(functools.partial(_decode, cfg))
        state = transformer.init_state(cfg, SLOTS, MAX_LEN,
                                       dtype=jnp.dtype(cfg.dtype))
        tok = jnp.asarray(rng.integers(0, cfg.vocab, (SLOTS, 1)), jnp.int32)
        lowered = step.lower(sess.params, tok, state, jnp.int32(0))
        has_kernel[spec.name] = "tpu_custom_call" in lowered.as_text()
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t1 = time.perf_counter()
        logits = jax.block_until_ready(
            compiled(sess.params, tok, state, jnp.int32(0)))
        t2 = time.perf_counter()
        check(logits.shape == (SLOTS, 1, cfg.vocab),
              f"{spec.name}: logits shape {logits.shape}")
        check(bool(jnp.isfinite(logits).all()),
              f"{spec.name}: non-finite logits")
        print(f"[tiers] {spec.name} ({spec.policy}): pallas="
              f"{has_kernel[spec.name]}, logits finite, compile "
              f"{t1 - t0:.1f}s, first step {t2 - t1:.3f}s", flush=True)
    return has_kernel


def _decode(cfg, params, tok, state, pos):
    from repro.models import transformer

    logits, _ = transformer.decode_step(params, cfg, {"token": tok}, state,
                                        pos)
    return logits


# -- phase 5: serve ----------------------------------------------------------

def prompt_lengths(n: int):
    """``n`` seeded prompt lengths in ``PROMPT_LENS``; at least one must
    span several chunks and end on a ragged one."""
    lo, hi = PROMPT_LENS
    out = [int(L) for L in np.random.default_rng(SEED).integers(lo, hi + 1, n)]
    check(any(L > PREFILL_CHUNK and L % PREFILL_CHUNK for L in out),
          f"seed {SEED} draws no multi-chunk ragged prompt: {out}")
    return out


def serve_and_check(sess, tiers):
    """Serve ``REQUESTS_PER_TIER`` requests per tier through the engine
    and check them; returns the number of tokens served."""
    vocab, new_tokens = sess.config.vocab, NEW_TOKENS
    rng = np.random.default_rng(SEED + 1)
    eng = sess.serving_engine(tiers, slots=SLOTS, max_len=MAX_LEN,
                              prefill_chunk=PREFILL_CHUNK)
    lengths = prompt_lengths(REQUESTS_PER_TIER * len(tiers))
    reqs = [eng.submit(rng.integers(0, vocab, L),
                       tier=tiers[i % len(tiers)].name,
                       max_new_tokens=new_tokens)
            for i, L in enumerate(lengths)]
    print(f"[serve] {len(reqs)} requests, prompt lengths {lengths}",
          flush=True)
    t0 = time.perf_counter()
    stats = eng.run()
    dt = time.perf_counter() - t0
    for r in reqs:
        toks = np.asarray(r.tokens)
        check(r.done and toks.shape == (new_tokens,),
              f"{r.id} ({r.tier}): {toks.size}/{new_tokens} tokens")
        check(((toks >= 0) & (toks < vocab)).all(),
              f"{r.id} ({r.tier}): token outside [0, {vocab})")
    n_tok = sum(len(r.tokens) for r in reqs)
    print(f"[serve] engine: {n_tok} tokens in {dt:.1f}s "
          f"({n_tok / dt:.1f} tokens/s, compiles included)", flush=True)
    for spec in tiers:
        s = stats[spec.name]
        print(f"[serve]   {spec.name}: {s.n_finished} finished, "
              f"{s.n_prefill_chunks} prefill chunks, {s.n_decode_steps} "
              f"decode steps", flush=True)
    for spec in tiers:
        r = max((r for r in reqs if r.tier == spec.name),
                key=lambda r: r.prompt.shape[0])
        t0 = time.perf_counter()
        solo = sess.replace(policy=spec.policy).generate(
            prompts=r.prompt[None], gen_len=new_tokens)
        dt = time.perf_counter() - t0
        check(np.array_equal(solo.tokens[0], r.tokens),
              f"{spec.name}: served tokens {r.tokens} differ from "
              f"Session.generate {solo.tokens[0].tolist()} "
              f"(prompt length {r.prompt.shape[0]})")
        print(f"[serve] {spec.name}: request {r.id} (prompt "
              f"{r.prompt.shape[0]}) == Session.generate ({dt:.1f}s)",
              flush=True)
    return n_tok


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"[device] platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if dev.platform != "tpu":
        print(f"error: chip_smoke.py needs a TPU, JAX found "
              f"{dev.platform!r}", file=sys.stderr)
        return 1

    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import DEFAULT_TIERS

    print(f"[cache] persistent compile cache: {enable_compile_cache()}",
          flush=True)
    clock = CompileClock()
    phases = {}

    t0 = time.perf_counter()
    check_kernels(get_arch(ARCH))
    phases["kernels"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    sess = build_session()
    phases["session"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    has_kernel = check_tiers(sess, DEFAULT_TIERS)
    for spec in DEFAULT_TIERS:
        want = spec.policy != "exact"
        check(has_kernel[spec.name] == want,
              f"{spec.name} ({spec.policy}): lowered decode "
              f"{'lacks' if want else 'holds'} tpu_custom_call")
    phases["tiers"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    serve_and_check(sess, DEFAULT_TIERS)
    phases["serve"] = time.perf_counter() - t0

    print(f"[timing] informational, not benchmark numbers: seconds per "
          f"phase {json.dumps(phases)}; {clock.count} backend compiles, "
          f"{clock.seconds:.1f}s; peak_bytes_in_use={peak_bytes()}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
