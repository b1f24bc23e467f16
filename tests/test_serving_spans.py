"""The engine's host spans, read back from a profiler trace.

A tiny real engine (reduced qwen3-4b, one exact lane) runs under
``jax.profiler`` on the CPU, its runner wrapped so that each call opens
an enclosing ``<tier>.decode`` / ``<tier>.prefill`` span as a caller that
names the lane would.  The trace, read with ``jax.profiler.ProfileData``,
holds every span of ``repro.serving.SPANS``, with the runner's launch and
sync inside the caller's decode span.
"""
import glob
import os

import numpy as np


class _LaneSpans:
    """A runner whose decode and prefill chunks run under a span named
    after the lane."""

    def __init__(self, runner, tier):
        self._r, self._tier = runner, tier

    def __getattr__(self, name):
        return getattr(self._r, name)

    def decode(self, tokens, pos, tables):
        import jax

        with jax.profiler.TraceAnnotation(f"{self._tier}.decode"):
            return self._r.decode(tokens, pos, tables)

    def prefill_chunk_step(self, prompt, start, end, table_row):
        import jax

        with jax.profiler.TraceAnnotation(f"{self._tier}.prefill"):
            return self._r.prefill_chunk_step(prompt, start, end, table_row)


def _host_spans(trace_dir, names) -> dict:
    """name -> [(t0, t1) ns] of the host events named in ``names``."""
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    t0 = float(e.start_ns)
                    out.setdefault(e.name, []).append(
                        (t0, t0 + float(e.duration_ns)))
    return out


def _inside(span, outer):
    return any(a <= span[0] and span[1] <= b for a, b in outer)


def test_engine_spans_reach_the_trace(tmp_path):
    import jax

    from repro.serving import SPANS, Engine, TierSpec, TransformerRunner
    from repro.session import Session

    sess = Session("qwen3-4b")  # reduced config, seeded params
    runner = _LaneSpans(
        TransformerRunner(sess.config, sess.params, 2, 32, page_size=4,
                          prefill_chunk=4), "premium")
    eng = Engine({"premium": runner}, (TierSpec("premium"),))
    # a 6-token prompt takes two chunks, so a prefill runs beside a decode
    reqs = [eng.submit(np.arange(1, 4), max_new_tokens=3),
            eng.submit(np.arange(1, 7), max_new_tokens=2)]
    jax.profiler.start_trace(str(tmp_path))
    eng.run()
    jax.profiler.stop_trace()
    assert all(r.done for r in reqs)

    by_name = _host_spans(str(tmp_path),
                          set(SPANS) | {"premium.decode", "premium.prefill"})
    assert set(SPANS) <= set(by_name)
    decodes = by_name["premium.decode"]
    assert len(decodes) == eng.lane_stats()["premium"].n_decode_steps
    # each decode call holds exactly its own launch and sync
    for d in decodes:
        for name in ("engine.launch", "engine.sync"):
            assert sum(1 for e in by_name[name] if _inside(e, [d])) == 1
    # every launch is a decode's or a prefill chunk's
    chunks = by_name["premium.prefill"]
    assert all(_inside(e, decodes + chunks)
               for e in by_name["engine.launch"])
    # the engine's own phases sit beside, not inside, the runner's calls
    for name in ("engine.admit", "engine.batch", "engine.land"):
        assert not any(_inside(e, decodes) for e in by_name[name])
