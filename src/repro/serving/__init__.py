"""Serving layer: continuous batching over Session with accuracy-tiered SLAs.

>>> from repro.session import Session
>>> from repro.serving import Engine, DEFAULT_TIERS
>>> eng = Engine.from_session(Session("qwen3-4b"), slots=4, max_len=64)
>>> r = eng.submit(prompt, tier="premium", max_new_tokens=16)
>>> eng.run()
>>> r.result()          # bit-identical to a solo Session.generate

Design: ``docs/serving.md``.  Scheduling/queueing in
:mod:`repro.serving.scheduler`, the paged KV cache in
:mod:`repro.serving.kvcache`, the batching loop in
:mod:`repro.serving.engine`.
"""
from repro.serving.engine import (SPANS, Engine, Event, ModelRunner,
                                  TierStats, TransformerRunner)
from repro.serving.kvcache import (PageAllocator, ServingError, SlotAllocator,
                                   gather_state, paged_layout,
                                   paged_pool_init, pages_for, scatter_chunk,
                                   scatter_token, write_state, zero_pages)
from repro.serving.scheduler import (DEFAULT_TIERS, FakeClock, MonotonicClock,
                                     Request, Scheduler, TierSpec)

__all__ = [
    "DEFAULT_TIERS",
    "Engine",
    "Event",
    "FakeClock",
    "ModelRunner",
    "MonotonicClock",
    "PageAllocator",
    "Request",
    "Scheduler",
    "ServingError",
    "SPANS",
    "SlotAllocator",
    "TierSpec",
    "TierStats",
    "TransformerRunner",
    "gather_state",
    "paged_layout",
    "paged_pool_init",
    "pages_for",
    "scatter_chunk",
    "scatter_token",
    "write_state",
    "zero_pages",
]
