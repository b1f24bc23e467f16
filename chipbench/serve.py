"""Drive the program's serving engine through a schedule, on the host clock.

The benchmark builds the engine through ``Session.serving_engine`` and
wraps each lane's runner in a :class:`SpannedRunner`, which opens a
``jax.profiler.TraceAnnotation`` around every call into the model (so a
trace can say what the host was doing in each device gap) and records each
call's shape (so the trace's kernel and module events can be counted and
costed).  The program is not edited: the runner protocol is duck-typed.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Call:
    """One call into a lane's runner."""

    kind: str          # decode | chunk | retire
    tier: str
    t0: float
    t1: float
    rows: int = 0      # decode: rows with a live request
    ctx: tuple = ()    # decode: live rows' positions; chunk: (start, end)


class SpannedRunner:
    """A lane runner with host spans and a call record."""

    def __init__(self, runner, tier: str, passes: int, calls: list):
        self._r = runner
        self.tier = tier
        self.passes = passes   # 0: exact (XLA), else segmented MXU passes
        self._calls = calls

    def __getattr__(self, name):
        return getattr(self._r, name)

    def prefill_chunk_step(self, prompt, start, end, table_row):
        t0 = time.monotonic()
        with TraceAnnotation(f"{self.tier}.prefill"):
            out = self._r.prefill_chunk_step(prompt, start, end, table_row)
        self._calls.append(Call("chunk", self.tier, t0, time.monotonic(),
                                ctx=(int(start), int(end))))
        return out

    def prefill_full(self, slot, prompt, table_row):
        raise NotImplementedError("the benchmark's configurations prefill "
                                  "in chunks")

    def decode(self, tokens, pos, tables):
        t0 = time.monotonic()
        with TraceAnnotation(f"{self.tier}.decode"):
            out = self._r.decode(tokens, pos, tables)
        live = np.asarray(tables)[:, 0] != self._r.n_pages
        self._calls.append(Call("decode", self.tier, t0, time.monotonic(),
                                rows=int(live.sum()),
                                ctx=tuple(int(p) for p in
                                          np.asarray(pos)[live])))
        return out

    def zero_pages(self, pages):
        t0 = time.monotonic()
        with TraceAnnotation(f"{self.tier}.retire"):
            self._r.zero_pages(pages)
        self._calls.append(Call("retire", self.tier, t0, time.monotonic()))


def tier_passes(policy: str) -> int:
    return 0 if policy == "exact" else int(policy.removeprefix("segmented"))


def tier_specs(mix: dict) -> list:
    """The mix's tiers, in priority order."""
    from repro.serving import TierSpec

    return [TierSpec(t["name"], t["policy"], priority=i)
            for i, t in enumerate(mix["engine"]["tiers"])]


def build_engine(session, mix: dict, calls: list, wrap=SpannedRunner):
    """The program's engine over ``session``'s resident weights, built as
    ``Session.serving_engine`` builds it with the mix's engine settings,
    each lane's runner then wrapped in ``wrap``; returns the engine and
    its wrapped runners by tier."""
    eng = mix["engine"]
    engine = session.serving_engine(
        tier_specs(mix), slots=eng["slots"], max_len=eng["max_len"],
        page_size=eng["page_size"], prefill_chunk=eng["prefill_chunk"],
        prefill_cache=eng["prefill_cache"])
    policy = {t["name"]: t["policy"] for t in eng["tiers"]}
    runners = {}
    # the engine has no public accessor for its lanes' runners yet
    for name, lane in engine._lanes.items():
        lane.runner = wrap(lane.runner, name, tier_passes(policy[name]),
                           calls)
        runners[name] = lane.runner
    return engine, runners


def warm(runners: dict, mix: dict, planned: list) -> None:
    """Run every program shape the planned requests reach, once, on the
    null page: one decode per lane, one final prefill chunk of each length
    they prefill, one re-zeroing of each page count they free.  Every seed
    plans the same sizes, so every seed warms the same shapes."""
    from chipbench.traffic import chunk_lengths, page_counts

    for r in runners.values():
        null_row = np.full(r.max_pages, r.n_pages, np.int32)
        zeros = np.zeros(r.n_slots, np.int32)
        r.decode(zeros, zeros, np.tile(null_row, (r.n_slots, 1)))
        for c in chunk_lengths(mix, planned):
            r.prefill_chunk_step(np.zeros(c, np.int32), 0, c, null_row)
        for k in page_counts(mix, planned):
            r.zero_pages(np.full(k, r.n_pages, np.int32))
    block(runners)


def block(runners: dict) -> None:
    """Wait until every lane's pool has been written (no work in flight)."""
    import jax

    for r in runners.values():
        jax.block_until_ready(r.pool)


@dataclasses.dataclass
class Served:
    """What a run saw, on the host clock (``time.monotonic``)."""

    planned: dict          # id -> Planned
    submitted: dict        # id -> engine Request
    due: dict              # id -> absolute due time
    tokens: dict           # id -> [event time of each token]
    w0: float = 0.0        # window start
    w1: float = 0.0        # end of the window's last step
    late: list = dataclasses.field(default_factory=list)  # submit - due, s


class Driver:
    """Feeds the schedule to the engine: open-loop requests are submitted
    at the first step boundary after they fall due; a backlog before the
    first step."""

    def __init__(self, engine, schedule: list):
        self.engine = engine
        self.pending = sorted(schedule, key=lambda p: p.due)
        self.s = Served(planned={p.id: p for p in schedule}, submitted={},
                        due={}, tokens={})
        self.t_zero = None

    def start(self, t_zero: float) -> None:
        """Fix the window's start on the clock; due times are relative."""
        self.t_zero = t_zero

    def _submit_due(self, now: float) -> None:
        while self.pending and self.t_zero + self.pending[0].due <= now:
            p = self.pending.pop(0)
            due = self.t_zero + p.due
            with TraceAnnotation("generator.submit"):
                self.s.submitted[p.id] = self.engine.submit(
                    p.prompt, tier=p.tier, max_new_tokens=p.max_new,
                    request_id=p.id)
            self.s.due[p.id] = due
            if np.isfinite(p.due):
                self.s.late.append(now - due)
            self.s.tokens[p.id] = []

    def step(self, until: float) -> bool:
        """One step of the loop; False once ``until`` has passed."""
        now = time.monotonic()
        if now >= until:
            return False
        self._submit_due(now)
        if self.engine.idle:
            nxt = (self.t_zero + self.pending[0].due if self.pending
                   else until)
            with TraceAnnotation("generator.wait"):
                time.sleep(max(0.0, min(nxt, until) - now))
            return True
        with TraceAnnotation("engine.step"):
            events = self.engine.step()
        for e in events:
            if e.kind == "token":
                self.s.tokens[e.request_id].append(e.time)
        return True

    def run_until(self, until: float, stop=None) -> None:
        while self.step(until):
            if stop is not None and stop():
                return

    def first_tokens(self) -> int:
        return sum(1 for t in self.s.tokens.values() if t)
