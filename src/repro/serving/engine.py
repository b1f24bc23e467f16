"""Continuous-batching serving engine with accuracy-tiered SLAs.

One engine serves many concurrent requests over ONE set of resident
weights.  Each accuracy tier (``premium`` exact, ``bulk`` segmented, …)
owns a **lane**: a paged KV pool (:mod:`repro.serving.kvcache`) plus one
resident compiled ``decode_step`` closed over that tier's
:class:`~repro.core.policy.NumericsPolicy` — the policy is established by
``numerics_scope`` inside ``transformer.backbone``, so routing a request
to a tier is just routing it to a lane.  Per engine step:

1. **admit** — a request is admitted when a decode row AND its full
   worst-case page reservation (``prompt + max_new - 1`` positions) are
   both available; admission is head-of-line in scheduler order, so a
   large request is never starved by smaller queue-jumpers.
2. **prefill** — every admitted-but-unprefilled prompt advances ONE
   ``prefill_chunk``-sized chunk (its last chunk lands the first token),
   so a long prompt's prefill interleaves with the lane's decode steps
   instead of stalling them;
3. **decode** — every lane with active requests runs ONE resident
   ``decode_step`` over its whole pool: gather through the per-row page
   tables, step, scatter the new cache rows back (inactive rows scatter
   into the null page);
4. **retire** — requests reaching ``max_new_tokens``/EOS free their row
   and pages the same step; freed pages are re-zeroed before reuse.

Continuous batching never changes a request's numerics: every token is
bit-identical to a solo ``Session.generate`` of the same prompt under the
same policy.  Paging only relocates cache rows (the gathered view holds
the identical bits), and a chunked prefill reproduces the solo prefill's
activations chunk-by-chunk (store-then-read bf16 equals the solo path's
single rounding; positions past the frontier mask to exact-zero softmax
weight) — asserted on the real model in
``tests/test_serving_numerics.py`` and under randomized memory pressure
in ``tests/test_serving_paging.py``.

Streaming: ``submit(..., on_token=cb)`` fires ``cb(request, token,
done)`` as tokens land; ``step()`` also returns the step's
:class:`Event` list for poll-style consumers.

Tracing: each host phase of a step runs under a fixed-name
:func:`span` (:data:`SPANS`) on the profiler's clock, so a device trace
can say what the host did in each device idle gap; with the profiler
off a span costs one flag check.  :class:`TierStats` integrates the
queue and prefill depths on the engine's clock (``queued_s``,
``prefilling_s``).

The engine is model-agnostic behind the :class:`ModelRunner` duck type,
so the scheduler/batching/paging logic is testable with a pure-Python
stub and no compilation (``tests/serving_sim.py``).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Mapping, Optional, Sequence

import numpy as np

from repro.serving.kvcache import (PageAllocator, ServingError, SlotAllocator,
                                   pages_for)
from repro.serving.scheduler import (DEFAULT_TIERS, MonotonicClock, Request,
                                     Scheduler, TierSpec)

__all__ = ["Engine", "Event", "ModelRunner", "SPANS", "TransformerRunner",
           "TierStats"]

#: The engine's host spans, one per phase of a step (see :func:`span`).
SPANS = ("engine.admit", "engine.prefill", "engine.batch", "engine.launch",
         "engine.sync", "engine.land", "engine.retire")


def span(name: str):
    """A host span on the profiler's clock, named by one of :data:`SPANS`
    (fixed strings: the lane is the enclosing caller's to name).  With the
    profiler off it records nothing.  jax is imported here, not with the
    module, so the stub-engine tests load it only when they step."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class ModelRunner:
    """What a lane needs from a model (duck-typed; this class is the
    documentation).

    Sizing: ``n_slots`` decode rows (the batch axis of the resident
    decode), ``max_len`` the per-request position cap, ``page_size``
    tokens per KV page, ``n_pages`` physical pages in the lane's pool
    (page id ``n_pages`` is the null page), ``prefill_chunk`` tokens per
    prefill chunk, and ``chunked`` False when the arch's recurrent state
    forces whole-prompt prefill (:meth:`prefill_full`).

    All page tables are int32 vectors of physical page ids, null-filled
    (``n_pages``) past the request's allocation; ``tables`` in
    :meth:`decode` stacks one per row, ``(n_slots, max_pages)``.
    """

    n_slots: int
    max_len: int
    page_size: int
    n_pages: int
    prefill_chunk: int
    chunked: bool = True

    @property
    def max_pages(self) -> int:
        """Longest page table a single request can need."""
        return pages_for(self.max_len, self.page_size)

    def pages_for(self, n_positions: int) -> int:
        return pages_for(n_positions, self.page_size)

    def prefill_chunk_step(self, prompt, start: int, end: int, table_row):
        """Prefill prompt positions ``[start, end)`` into the pages of
        ``table_row``; returns the first generated token when ``end``
        completes the prompt, else None."""
        raise NotImplementedError

    def prefill_full(self, slot: int, prompt, table_row):
        """Whole-prompt fallback (archs with non-paged recurrent state):
        prefill the full prompt, install it into ``table_row``'s pages +
        per-slot row ``slot``, return the first token."""
        raise NotImplementedError

    def decode(self, tokens, pos, tables):
        """Advance the WHOLE pool one step from per-row last tokens and
        absolute positions (``(n_slots,)`` int32) through per-row page
        tables; returns the per-row next tokens."""
        raise NotImplementedError

    def zero_pages(self, pages) -> None:
        """Re-zero freed physical pages before they can be reused."""
        raise NotImplementedError


class TransformerRunner(ModelRunner):
    """The real lane runner: resident paged pool + one jitted decode per
    tier.

    The decode closure (gather pages -> ``decode_step`` -> scatter the
    new rows back) is compiled ONCE per lane for the fixed pool shape and
    stays resident; prefill compiles per CHUNK shape, not per prompt
    length — ragged production traffic shares ``ceil(max_len /
    prefill_chunk)``-ish chunk shapes instead of pinning one executable
    per observed length.  The chunk-shape cache is still LRU-bounded
    (``prefill_cache_size``) and each entry owns a private ``jax.jit``
    wrapper, so eviction actually releases the compiled executable.
    Greedy argmax happens outside the jit, mirroring ``Session.generate``
    so the token stream is bit-comparable.

    Archs with SSM/conv blocks keep a per-slot recurrent state that
    cannot be re-entered chunk-by-chunk without changing scan numerics,
    so they fall back to whole-prompt prefill (``chunked`` False; the
    compiled-prefill cache is then keyed per prompt length as before).
    """

    #: Default LRU bound on jitted prefill shapes (chunk shapes, plus
    #: whole-prompt lengths for non-chunkable archs).
    PREFILL_CACHE_SIZE = 32
    #: Default tokens per KV page.
    PAGE_SIZE = 16
    #: Default tokens prefilled per engine step per request.
    PREFILL_CHUNK = 32

    def __init__(self, cfg, params, n_slots: int, max_len: int, *,
                 page_size: Optional[int] = None,
                 pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_cache_size: Optional[int] = None):
        import jax

        from repro.models import transformer
        from repro.serving import kvcache

        if cfg.encoder_layers:
            raise ServingError(
                f"{cfg.arch_id}: encoder-decoder archs are not servable by "
                f"the token-only engine (requests carry no encoder inputs)")
        if prefill_cache_size is None:
            prefill_cache_size = self.PREFILL_CACHE_SIZE
        if prefill_cache_size < 1:
            raise ServingError(
                f"prefill_cache_size must be >= 1, got {prefill_cache_size}")
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.page_size = int(page_size or self.PAGE_SIZE)
        self.prefill_chunk = int(prefill_chunk or self.PREFILL_CHUNK)
        if self.page_size < 1:
            raise ServingError(
                f"page_size must be >= 1, got {self.page_size}")
        if self.prefill_chunk < 1:
            raise ServingError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        # default pool: capacity parity with the old whole-max_len slots
        self.n_pages = int(pages if pages is not None
                           else n_slots * self.max_pages)
        self._layout = kvcache.paged_layout(cfg)
        self.pool = kvcache.paged_pool_init(cfg, n_slots, self.n_pages,
                                            self.page_size)
        # chunked prefill re-enters decode_step per chunk, which only the
        # sequence-axis (paged) caches support; any per-slot recurrent
        # leaf forces the whole-prompt fallback
        self.chunked = all(pi in self._layout[si]
                           for si, seg in enumerate(self.pool["layers"])
                           for pi in seg)
        ps = self.page_size

        def _decode(p, tok, pool, tables, pos):
            dense = kvcache.gather_state(pool, self._layout, tables)
            with jax.named_scope("model"):
                logits, new = transformer.decode_step(p, cfg, {"token": tok},
                                                      dense, pos)
            pool = kvcache.scatter_token(pool, self._layout, new, tables,
                                         pos, ps)
            return logits, pool

        self._decode = jax.jit(_decode)
        # compile-shape key -> private jitted fn, LRU order (LRU first);
        # keys: ("chunk", chunk_len) / ("full", prompt_len)
        self._prefill = collections.OrderedDict()
        self._prefill_cache_size = prefill_cache_size

    # -- compiled-shape LRU --------------------------------------------------

    def _jitted(self, key, make):
        fn = self._prefill.get(key)
        if fn is None:
            fn = make()
            self._prefill[key] = fn
            while len(self._prefill) > self._prefill_cache_size:
                self._prefill.popitem(last=False)
        else:
            self._prefill.move_to_end(key)
        return fn

    # -- ModelRunner protocol ------------------------------------------------

    def prefill_chunk_step(self, prompt, start: int, end: int, table_row):
        import jax
        import jax.numpy as jnp

        from repro.models import transformer
        from repro.serving import kvcache

        prompt = np.asarray(prompt, np.int32)
        c = int(end) - int(start)
        ps = self.page_size

        def make():
            def _chunk(p, tok, pool, trow, off):
                dense = kvcache.gather_state(pool, self._layout, trow[None])
                with jax.named_scope("model"):
                    logits, new = transformer.decode_step(
                        p, self.cfg, {"token": tok}, dense, off)
                pool = kvcache.scatter_chunk(pool, self._layout, new, trow,
                                             off, c, ps)
                return logits, pool

            return jax.jit(_chunk)

        with span("engine.launch"):
            fn = self._jitted(("chunk", c), make)
            logits, self.pool = fn(
                self.params, jnp.asarray(prompt[start:end])[None], self.pool,
                jnp.asarray(table_row, jnp.int32),
                jnp.asarray(start, jnp.int32))
        if int(end) == prompt.shape[0]:
            with span("engine.sync"):
                return int(jnp.argmax(logits[:, -1:], axis=-1)[0, 0])
        return None

    def prefill_full(self, slot: int, prompt, table_row):
        import jax
        import jax.numpy as jnp

        from repro.models import transformer
        from repro.serving import kvcache

        prompt = np.asarray(prompt, np.int32)
        L = int(prompt.shape[0])
        # buffer exactly the pages the prompt occupies: write_state
        # scatters every buffered position, so the buffer must not
        # overrun the live page-table entries
        ml = self.pages_for(L) * self.page_size
        ps = self.page_size

        def make():
            def _full(p, tokens, pool, trow, sl):
                logits, state = transformer.prefill(
                    p, self.cfg, {"tokens": tokens}, max_len=ml)
                pool = kvcache.write_state(pool, self._layout, state, sl,
                                           trow, ps)
                return logits, pool

            return jax.jit(_full)

        with span("engine.launch"):
            fn = self._jitted(("full", L), make)
            logits, self.pool = fn(
                self.params, jnp.asarray(prompt)[None], self.pool,
                jnp.asarray(table_row, jnp.int32),
                jnp.asarray(slot, jnp.int32))
        with span("engine.sync"):
            return int(jnp.argmax(logits[:, -1:], axis=-1)[0, 0])

    def decode(self, tokens, pos, tables):
        import jax.numpy as jnp

        with span("engine.launch"):
            logits, self.pool = self._decode(
                self.params, jnp.asarray(tokens, jnp.int32)[:, None],
                self.pool, jnp.asarray(tables, jnp.int32),
                jnp.asarray(pos, jnp.int32))
        with span("engine.sync"):
            return np.asarray(jnp.argmax(logits[:, -1:], axis=-1),
                              np.int32)[:, 0]

    def zero_pages(self, pages) -> None:
        from repro.serving import kvcache

        if len(pages) == 0:
            return
        self.pool = kvcache.zero_pages(self.pool, self._layout,
                                       np.asarray(pages, np.int32))


@dataclasses.dataclass(frozen=True)
class Event:
    """One streaming event: ``admit`` (row + page reservation granted),
    ``token`` (one generated token, the prefill token included) or
    ``finish``."""

    kind: str
    request_id: str
    tier: str
    step: int
    time: float
    token: Optional[int] = None


@dataclasses.dataclass
class TierStats:
    n_finished: int = 0
    n_tokens: int = 0
    n_decode_steps: int = 0
    occupancy_sum: int = 0      # active requests summed over decode steps
    n_prefill_chunks: int = 0   # prefill calls (chunks, or whole prompts)
    pages_reserved_sum: int = 0  # reserved pages summed over retired requests
    # steps that ran prefill chunks WHILE this lane also decoded — the
    # interleave chunked prefill exists to provide
    n_interleave_steps: int = 0
    # steps where active decoders stalled with no decode batch (must stay
    # 0: chunked prefill never preempts a lane's decode)
    n_decode_stall_steps: int = 0
    # request-seconds on the engine's clock: submitted but not admitted,
    # and admitted without a first token (over a window: mean queue and
    # prefill depth; over the window's arrivals: mean queue wait)
    queued_s: float = 0.0
    prefilling_s: float = 0.0

    @property
    def mean_occupancy(self) -> float:
        return (self.occupancy_sum / self.n_decode_steps
                if self.n_decode_steps else 0.0)

    @property
    def pages_per_request(self) -> float:
        """Mean KV pages reserved per retired request — the paged pool's
        footprint metric (a whole-``max_len`` slot design pins
        ``max_pages`` for every request)."""
        return (self.pages_reserved_sum / self.n_finished
                if self.n_finished else 0.0)


@dataclasses.dataclass
class _Lane:
    spec: TierSpec
    runner: ModelRunner
    alloc: SlotAllocator        # decode rows (cheap, no KV storage)
    pages: PageAllocator        # KV pages (the real capacity)
    active: dict                # slot -> Request (decoding)
    prefilling: dict            # slot -> Request (admitted, prompt pending)
    stats: TierStats


class Engine:
    """The continuous-batching serving engine (see module docstring)."""

    def __init__(self, runners: Mapping[str, ModelRunner],
                 tiers: Optional[Sequence[TierSpec]] = None,
                 *, clock=None, aging: Optional[float] = None):
        tiers = tuple(tiers) if tiers is not None else tuple(
            TierSpec(name, priority=i)
            for i, name in enumerate(runners))
        by_name = {t.name: t for t in tiers}
        if set(by_name) != set(runners):
            raise ServingError(
                f"tier specs {sorted(by_name)} do not match runners "
                f"{sorted(runners)}")
        self.clock = clock if clock is not None else MonotonicClock()
        self.scheduler = Scheduler(tuple(by_name), aging=aging)
        self._lanes = {
            name: _Lane(spec=by_name[name], runner=runner,
                        alloc=SlotAllocator(runner.n_slots),
                        pages=PageAllocator(runner.n_pages),
                        active={}, prefilling={}, stats=TierStats())
            for name, runner in runners.items()
        }
        self._step = 0
        self._n_submitted = 0
        self._inflight: dict = {}  # request_id -> Request (queued or active)
        self._t_depths = self.clock.now()  # queued_s/prefilling_s are to here

    # -- construction -------------------------------------------------------

    @classmethod
    def from_session(cls, session, tiers: Sequence[TierSpec] = DEFAULT_TIERS,
                     *, slots: int = 4, max_len: int = 64,
                     page_size: Optional[int] = None,
                     pages: Optional[int] = None,
                     prefill_chunk: Optional[int] = None, clock=None,
                     aging: Optional[float] = None,
                     prefill_cache: Optional[int] = None) -> "Engine":
        """Build real lanes over a :class:`repro.session.Session`: one
        :class:`TransformerRunner` per tier, every tier's config sharing
        the session's resident params (tier policies go through the same
        coercion as ``Session(policy=...)``).

        ``page_size`` (default :data:`TransformerRunner.PAGE_SIZE`) sets
        the KV page granularity and ``pages`` the per-tier physical pool
        (default: ``slots * ceil(max_len / page_size)``, capacity parity
        with whole-``max_len`` slots); ``prefill_chunk`` (default
        :data:`TransformerRunner.PREFILL_CHUNK`) bounds the prompt tokens
        prefilled per engine step; ``prefill_cache`` bounds each lane's
        compiled-prefill-shape cache (LRU, default
        :data:`TransformerRunner.PREFILL_CACHE_SIZE`)."""
        runners = {}
        for spec in tiers:
            tier_sess = session.replace(policy=spec.policy)
            runners[spec.name] = TransformerRunner(
                tier_sess.config, session.params, slots, max_len,
                page_size=page_size, pages=pages,
                prefill_chunk=prefill_chunk,
                prefill_cache_size=prefill_cache)
        return cls(runners, tiers, clock=clock, aging=aging)

    # -- submission ---------------------------------------------------------

    @property
    def tiers(self) -> tuple:
        return tuple(self._lanes)

    def lane_stats(self) -> dict:
        """Each lane's :class:`TierStats`, the depth integrals brought up to
        now."""
        self._advance_depths()
        return {name: lane.stats for name, lane in self._lanes.items()}

    def _advance_depths(self) -> float:
        """Integrate every lane's queue and prefill depth up to now; called
        before any change of either, at each step's start and by
        :meth:`lane_stats` (so a snapshot is current).  Returns now."""
        now = self.clock.now()
        dt = now - self._t_depths
        if dt:
            for name, lane in self._lanes.items():
                lane.stats.queued_s += dt * self.scheduler.pending(name)
                lane.stats.prefilling_s += dt * len(lane.prefilling)
            self._t_depths = now
        return now

    def submit(self, prompt, tier: Optional[str] = None,
               max_new_tokens: int = 16, *, request_id: Optional[str] = None,
               priority: Optional[int] = None, on_token=None,
               eos_id: Optional[int] = None) -> Request:
        """Queue one request; returns the live :class:`Request` handle
        (its ``tokens``/``done`` fields update as the engine steps).

        ``eos_id`` retires the request as soon as it emits that token
        (the EOS is landed as the final token); its row and KV pages free
        the same step, so a waiting request can join the next admit pass.
        Early stopping never perturbs co-batched rows — tokens stay
        bit-identical to solo :meth:`repro.session.Session.generate`
        with the same ``eos_id``.
        """
        if tier is None:
            tier = next(iter(self._lanes))
        lane = self._lanes.get(tier)
        if lane is None:
            raise ServingError(f"unknown tier {tier!r}; engine serves "
                               f"{sorted(self._lanes)}")
        rid = request_id or f"r{self._n_submitted}"
        if rid in self._inflight:
            raise ServingError(
                f"request id {rid!r} is already in flight (tier "
                f"{self._inflight[rid].tier!r}); ids must be unique until "
                f"the request finishes")
        req = Request(
            id=rid,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            tier=tier,
            priority=(priority if priority is not None
                      else lane.spec.priority),
            on_token=on_token,
            eos_id=eos_id,
        )
        self._n_submitted += 1
        need = req.prompt.shape[0] + req.max_new_tokens - 1
        if need > lane.runner.max_len:
            raise ServingError(
                f"request {req.id!r} needs {need} cache positions "
                f"(prompt {req.prompt.shape[0]} + {req.max_new_tokens} new) "
                f"but tier {tier!r} pools max_len={lane.runner.max_len}")
        if lane.runner.pages_for(need) > lane.runner.n_pages:
            raise ServingError(
                f"request {req.id!r} needs {lane.runner.pages_for(need)} KV "
                f"pages ({need} positions / page_size "
                f"{lane.runner.page_size}) but tier {tier!r} pools "
                f"{lane.runner.n_pages} pages")
        self._inflight[rid] = req
        return self.scheduler.submit(req, self._advance_depths())

    # -- the serving loop ---------------------------------------------------

    def _emit(self, events, req, kind, token=None):
        now = self.clock.now()
        events.append(Event(kind=kind, request_id=req.id, tier=req.tier,
                            step=self._step, time=now, token=token))
        if kind == "token" and req.on_token is not None:
            req.on_token(req, token, req.complete)

    def _land_token(self, events, lane, req, token: int):
        req.tokens.append(int(token))
        lane.stats.n_tokens += 1
        self._emit(events, req, "token", token=int(token))
        # retire on the max-token cap OR the request's EOS stop token
        if req.complete:
            with span("engine.retire"):
                req.finish_time = self.clock.now()
                req.finish_step = self._step
                lane.alloc.free(req.slot)
                del lane.active[req.slot]
                freed = lane.pages.release(req.id)
                lane.runner.zero_pages(freed)
                req.pages = []
                lane.stats.pages_reserved_sum += req.n_reserved_pages
                self._inflight.pop(req.id, None)
                lane.stats.n_finished += 1
                self._emit(events, req, "finish")

    def _grow_pages(self, lane, req, n_positions: int):
        """Take physical pages (lazily, within the admission reservation)
        until ``req``'s table covers ``n_positions`` positions."""
        while len(req.pages) * lane.runner.page_size < n_positions:
            req.pages.append(lane.pages.take_page(req.id))

    def _table_row(self, runner, req):
        row = np.full(runner.max_pages, runner.n_pages, np.int32)
        row[:len(req.pages)] = req.pages
        return row

    def _prefill_one(self, events, lane, req):
        """Advance one request's prefill by one chunk (or the whole
        prompt on non-chunkable archs); lands the first token when the
        prompt completes."""
        runner = lane.runner
        L = req.prompt.shape[0]
        if runner.chunked:
            end = min(req.prefill_pos + runner.prefill_chunk, L)
            self._grow_pages(lane, req, end)
            token = runner.prefill_chunk_step(
                req.prompt, req.prefill_pos, end,
                self._table_row(runner, req))
            req.prefill_pos = end
        else:
            # whole-prompt fallback: the runner buffers pages_for(L)
            # full pages, so cover them all
            self._grow_pages(lane, req, runner.pages_for(L)
                             * runner.page_size)
            token = runner.prefill_full(req.slot, req.prompt,
                                        self._table_row(runner, req))
            req.prefill_pos = L
        lane.stats.n_prefill_chunks += 1
        if token is None:
            return
        self._advance_depths()
        del lane.prefilling[req.slot]
        req.pos = L
        lane.active[req.slot] = req
        self._land_token(events, lane, req, token)

    def step(self) -> list:
        """One engine step: admit -> advance prefills one chunk -> decode
        every lane -> retire.  Returns the step's events."""
        self._step += 1
        events = []
        now = self._advance_depths()
        ran_chunks = {}
        # decoders live BEFORE this step's prefill work: the interleave /
        # stall accounting is about what chunked prefill does to them
        had_active = {name: bool(lane.active)
                      for name, lane in self._lanes.items()}
        for name, lane in self._lanes.items():
            # admit while a row AND the head request's full page
            # reservation fit — head-of-line, so a big request is never
            # starved by smaller queue-jumpers behind it
            with span("engine.admit"):
                while lane.alloc.n_free and self.scheduler.pending(name):
                    head = self.scheduler.peek_next(name, now)
                    need = head.prompt.shape[0] + head.max_new_tokens - 1
                    n_need = lane.runner.pages_for(need)
                    if not lane.pages.can_reserve(n_need):
                        break
                    t_admit = self._advance_depths()
                    req = self.scheduler.pop_next(name, now)
                    lane.pages.reserve(req.id, n_need)
                    req.n_reserved_pages = n_need
                    req.slot = lane.alloc.alloc(req.id)
                    req.admit_time = t_admit  # where queued_s stops
                    req.admit_step = self._step
                    lane.prefilling[req.slot] = req
                    self._emit(events, req, "admit")
            # one prefill chunk per pending prompt, in admission order
            ran_chunks[name] = len(lane.prefilling)
            for req in [lane.prefilling[s] for s in list(lane.prefilling)]:
                with span("engine.prefill"):
                    self._prefill_one(events, lane, req)
        for name, lane in self._lanes.items():
            if not lane.active:
                # a lane whose decoders got no decode batch this step has
                # stalled — structurally impossible here (prefill chunks
                # never preempt decode), and gated at 0 in the bench
                if had_active[name]:
                    lane.stats.n_decode_stall_steps += 1
                continue
            if ran_chunks[name] and had_active[name]:
                lane.stats.n_interleave_steps += 1
            runner = lane.runner
            with span("engine.batch"):
                n = runner.n_slots
                tokens = np.zeros(n, np.int32)
                pos = np.zeros(n, np.int32)
                tables = np.full((n, runner.max_pages), runner.n_pages,
                                 np.int32)
                for slot, req in lane.active.items():
                    # this step writes cache position req.pos — make sure
                    # a physical page covers it (always within the
                    # reservation)
                    self._grow_pages(lane, req, req.pos + 1)
                    tokens[slot] = req.tokens[-1]
                    pos[slot] = req.pos
                    tables[slot, :len(req.pages)] = req.pages
            nxt = runner.decode(tokens, pos, tables)
            lane.stats.n_decode_steps += 1
            lane.stats.occupancy_sum += len(lane.active)
            with span("engine.land"):
                # iterate a snapshot: retirement mutates lane.active
                for slot, req in sorted(lane.active.items()):
                    req.pos += 1
                    self._land_token(events, lane, req, nxt[slot])
        return events

    @property
    def idle(self) -> bool:
        return (self.scheduler.pending() == 0
                and all(not l.active and not l.prefilling
                        for l in self._lanes.values()))

    def run(self, max_steps: int = 100_000) -> dict:
        """Step until every queued request has finished; returns
        ``lane_stats()``.  ``max_steps`` bounds the drain (a structured
        :class:`ServingError` instead of a hang)."""
        steps = 0
        while not self.idle:
            if steps >= max_steps:
                raise ServingError(
                    f"engine did not drain within {max_steps} steps "
                    f"({self.scheduler.pending()} queued, "
                    f"{sum(len(l.active) + len(l.prefilling) for l in self._lanes.values())} "
                    f"active)")
            self.step()
            steps += 1
        return self.lane_stats()
