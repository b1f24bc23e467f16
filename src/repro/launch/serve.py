"""Serving driver: batched greedy decoding over the continuous-batching
engine (:mod:`repro.serving`).

Demonstrates the paper's accuracy-configurable serving: the same weights
served under exact / segmented-3 / segmented-1 (ACL-like) numerics.
``serve()`` routes every prompt through :class:`repro.serving.Engine` —
one accuracy tier, ``batch`` KV slots, requests retired per-step — and
returns exactly the tokens a plain ``Session.generate`` would produce
(continuous batching is bit-transparent; asserted in
``tests/test_session.py`` and ``tests/test_serving_numerics.py``).  For
multi-tier SLAs (premium/standard/bulk in ONE engine) use
``python -m repro.session serve-loop`` or ``examples/serve_lm.py``.

``--policy policy.json`` serves under a per-layer
:class:`~repro.core.policy.NumericsPolicy` (e.g. one emitted by
``Session.auto_configure``; schema in ``docs/numerics_policy.md``) and
prints the modeled area / power / compute-latency of the resolved policy
(Table II roll-up over every call site via ``Session.ppa_report``).

A malformed or missing ``--policy`` file exits with a one-line error and
a non-zero status (no traceback).
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.session import Session, SessionError, print_ppa_report


def serve(arch: str = "qwen3-4b", batch: int = 4, prompt_len: int = 32,
          gen_len: int = 16, numerics: str = "exact", seed: int = 0,
          params=None, cfg=None, policy=None, reduced: bool = True):
    """Serve ``arch`` (or a ready config + params) through the
    continuous-batching engine and return the greedy continuations as a
    ``(batch, gen_len)`` int array — token-for-token what
    ``Session.generate`` yields for the same seed.  ``numerics`` is a
    preset name; ``policy`` (a NumericsPolicy or a JSON path) overrides
    it; ``reduced=False`` serves ``arch`` at its published widths."""
    from repro.serving import TierSpec

    sess = Session(cfg if cfg is not None else arch,
                   policy=policy if policy is not None else numerics,
                   seed=seed, params=params, reduced=reduced)
    label = "policy" if policy is not None else numerics
    if policy is not None:
        print_ppa_report(sess.ppa_report(), tag="serve")
    eng = sess.serving_engine((TierSpec("serve", policy=sess.numerics),),
                              slots=batch, max_len=prompt_len + gen_len)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, sess.config.vocab, (batch, prompt_len))
    t0 = time.perf_counter()
    reqs = [eng.submit(p, tier="serve", max_new_tokens=gen_len)
            for p in prompts]
    eng.run()
    dt = time.perf_counter() - t0
    print(f"[serve] {arch} numerics={label}: {batch}x{gen_len} tokens "
          f"in {dt:.2f}s ({batch * gen_len / dt:.1f} tok/s, "
          f"continuous batching)")
    return np.stack([r.result() for r in reqs])


def main(argv=None) -> int:
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import ServingError

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--numerics", default="exact",
                    choices=["exact", "segmented3", "segmented2", "segmented1"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--policy", default=None, metavar="POLICY_JSON",
                    help="serve under a per-layer NumericsPolicy (JSON file; "
                         "overrides --numerics)")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full arch config (default: reduced)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    try:
        serve(args.arch, batch=args.batch, gen_len=args.gen_len,
              numerics=args.numerics, policy=args.policy,
              reduced=not args.full_size)
    except (SessionError, ServingError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
