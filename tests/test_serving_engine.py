"""Deterministic serving-engine tests over the simulation rig.

Everything here runs on :class:`tests.serving_sim.StubRunner` — no jax
compilation — with scripted arrivals through a ``FakeClock``, so the
assertions are about the engine itself: admission order, mid-decode
joins, per-request retirement, KV slot reuse, starvation-freedom, the
event stream, and the submit-time validation contract.  Numerics (the
bit-equality of continuous batching to solo generation on the real
model) lives in ``tests/test_serving_numerics.py``.
"""
import numpy as np
import pytest

from repro.serving import (FakeClock, Request, Scheduler, ServingError,
                           TierSpec, TierStats)
from serving_sim import make_stub_engine, run_scripted, stub_reference


def _req(prompt, n=3, **kw):
    return dict(prompt=np.asarray(prompt, np.int32), max_new_tokens=n, **kw)


# ---------------------------------------------------------------------------
# scheduler + clock units
# ---------------------------------------------------------------------------

def test_fake_clock_is_manual_and_monotone():
    clk = FakeClock(start=5.0)
    assert clk.now() == 5.0
    assert clk.advance(2.5) == 7.5
    with pytest.raises(ServingError):
        clk.advance(-0.1)


def test_scheduler_orders_by_priority_then_arrival():
    sched = Scheduler(("a",))
    for i, prio in enumerate([2, 0, 1, 0]):
        sched.submit(Request(id=f"r{i}", prompt=[1], max_new_tokens=1,
                             tier="a", priority=prio), now=0.0)
    order = [sched.pop_next("a", now=0.0).id for _ in range(4)]
    assert order == ["r1", "r3", "r2", "r0"]  # prio asc, FIFO within prio
    assert sched.pop_next("a", now=0.0) is None


def test_scheduler_aging_promotes_to_priority_zero():
    sched = Scheduler(("a",), aging=10.0)
    old = sched.submit(Request(id="old", prompt=[1], max_new_tokens=1,
                               tier="a", priority=9), now=0.0)
    sched.submit(Request(id="new", prompt=[1], max_new_tokens=1,
                         tier="a", priority=0), now=9.0)
    # before the aging horizon the fresh priority-0 request wins ...
    assert sched.effective_priority(old, now=9.0) == 9
    assert sched.pop_next("a", now=9.0).id == "new"
    sched.submit(Request(id="new2", prompt=[1], max_new_tokens=1,
                         tier="a", priority=0), now=10.0)
    # ... at the horizon the old request is priority 0 and FIFO beats new2
    assert sched.effective_priority(old, now=10.0) == 0
    assert sched.pop_next("a", now=10.0).id == "old"


def test_scheduler_rejects_unknown_tier():
    sched = Scheduler(("a",))
    with pytest.raises(ServingError, match="unknown tier"):
        sched.submit(Request(id="r", prompt=[1], max_new_tokens=1,
                             tier="nope"), now=0.0)


# ---------------------------------------------------------------------------
# submit-time validation (structured errors, never an XLA shape error)
# ---------------------------------------------------------------------------

def test_submit_validation_contract():
    eng, _, _ = make_stub_engine(slots=1, max_len=8)
    with pytest.raises(ServingError, match="unknown tier"):
        eng.submit(np.array([1]), tier="nope")
    with pytest.raises(ServingError, match="empty prompt"):
        eng.submit(np.array([], np.int32))
    with pytest.raises(ServingError, match="max_new_tokens"):
        eng.submit(np.array([1]), max_new_tokens=0)
    with pytest.raises(ServingError, match="max_len=8"):
        eng.submit(np.arange(6), max_new_tokens=4)  # needs 9 > 8 positions
    # boundary: prompt_len + max_new - 1 == max_len is admissible
    eng.submit(np.arange(5), max_new_tokens=4)


def test_unfinished_result_raises():
    eng, _, _ = make_stub_engine()
    r = eng.submit(np.array([1, 2]), max_new_tokens=2)
    with pytest.raises(ServingError, match="not finished"):
        r.result()


def test_engine_rejects_mismatched_tier_specs():
    from repro.serving import Engine
    from serving_sim import StubRunner

    with pytest.raises(ServingError, match="do not match"):
        Engine({"a": StubRunner()}, (TierSpec("b"),))


# ---------------------------------------------------------------------------
# admission order
# ---------------------------------------------------------------------------

def test_admission_order_priority_then_fifo():
    eng, clock, _ = make_stub_engine(slots=1)
    # n=2 so each request occupies the slot for one decode step (an n=1
    # request retires inside the admit loop and the order would not show)
    r_lo = eng.submit(np.array([1]), max_new_tokens=2, priority=2)
    r_hi = eng.submit(np.array([2]), max_new_tokens=2, priority=0)
    r_hi2 = eng.submit(np.array([3]), max_new_tokens=2, priority=0)
    run_scripted(eng, clock, [])
    # priority admits first; FIFO within a priority; only then the laggard
    assert r_hi.admit_step < r_hi2.admit_step < r_lo.admit_step


def test_single_slot_serializes_requests():
    eng, clock, _ = make_stub_engine(slots=1)
    a = eng.submit(np.array([1, 2, 3]), max_new_tokens=3)
    b = eng.submit(np.array([4, 5]), max_new_tokens=2)
    run_scripted(eng, clock, [])
    assert a.done and b.done
    assert b.admit_step > a.finish_step  # b waited for the only slot


# ---------------------------------------------------------------------------
# continuous batching: mid-decode join, retirement, slot reuse
# ---------------------------------------------------------------------------

def test_mid_decode_join():
    eng, clock, runners = make_stub_engine(slots=2)
    long = eng.submit(np.array([1, 2, 3]), max_new_tokens=8)
    # late arrival two steps into long's decode
    reqs, _ = run_scripted(eng, clock, [[], [], [_req([7, 8], n=2)]])
    late = reqs[0]
    assert late.admit_step > long.admit_step      # joined mid-flight ...
    assert late.admit_step < long.finish_step     # ... while long was active
    assert late.finish_step < long.finish_step    # and retired first
    # the join really was batched: some decode call carried both positions
    runner = runners["a"]
    joint = [pos for _, pos in runner.decode_calls
             if (pos > 0).sum() == 2]
    assert joint, "expected at least one decode step with both slots active"
    np.testing.assert_array_equal(long.result(),
                                  stub_reference([1, 2, 3], 8))
    np.testing.assert_array_equal(late.result(), stub_reference([7, 8], 2))


def test_per_request_retirement_frees_slot_same_step():
    eng, clock, _ = make_stub_engine(slots=2)
    short = eng.submit(np.array([1]), max_new_tokens=1)   # prefill-only
    eng.step()
    assert short.done and short.finish_step == short.admit_step
    lane = eng._lanes["a"]
    assert lane.alloc.n_free == 2 and lane.active == {}


def test_kv_slot_reuse_after_retirement():
    eng, clock, runners = make_stub_engine(slots=1)
    a = eng.submit(np.array([1, 2]), max_new_tokens=2)
    b = eng.submit(np.array([9, 9, 9]), max_new_tokens=3)
    run_scripted(eng, clock, [])
    assert a.slot == b.slot == 0                  # the one slot, reused
    assert b.admit_step > a.finish_step
    # reuse did not leak a's state into b's stream
    np.testing.assert_array_equal(b.result(), stub_reference([9, 9, 9], 3))
    assert eng._lanes["a"].alloc.owners == {}     # drained clean


def test_eos_retires_early_with_truncated_result():
    prompt = np.array([3, 1, 4])
    ref = stub_reference(prompt, 8)
    eos = int(ref[2])                 # third token of the deterministic stream
    eng, clock, _ = make_stub_engine(slots=2)
    r = eng.submit(prompt, max_new_tokens=8, eos_id=eos)
    run_scripted(eng, clock, [])
    assert r.done and len(r.tokens) == 3          # stopped at the EOS
    np.testing.assert_array_equal(r.result(), ref[:3])
    assert r.result()[-1] == eos                  # EOS itself is landed


def test_eos_frees_slot_for_waiting_request():
    prompt = np.array([3, 1, 4])
    eos = int(stub_reference(prompt, 8)[1])
    eng, clock, _ = make_stub_engine(slots=1)
    a = eng.submit(prompt, max_new_tokens=8, eos_id=eos)
    b = eng.submit(np.array([9, 9]), max_new_tokens=2)
    run_scripted(eng, clock, [])
    # a stopped at step 2 of 8, so b admitted far earlier than a's cap
    assert len(a.tokens) == 2
    assert a.slot == b.slot == 0                  # slot recycled
    assert b.admit_step > a.finish_step
    np.testing.assert_array_equal(b.result(), stub_reference([9, 9], 2))


def test_eos_never_emitted_runs_to_cap():
    prompt = np.array([5, 6])
    ref = stub_reference(prompt, 4)
    eos = int(max(ref) + 1)                       # not in the stream
    eng, clock, _ = make_stub_engine(slots=1)
    r = eng.submit(prompt, max_new_tokens=4, eos_id=eos)
    run_scripted(eng, clock, [])
    np.testing.assert_array_equal(r.result(), ref)


def test_eos_on_token_callback_reports_done():
    prompt = np.array([2, 7, 1])
    ref = stub_reference(prompt, 8)
    eos = int(ref[1])
    seen = []
    eng, clock, _ = make_stub_engine(slots=1)
    eng.submit(prompt, max_new_tokens=8, eos_id=eos,
               on_token=lambda req, tok, done: seen.append((tok, done)))
    run_scripted(eng, clock, [])
    assert seen == [(int(ref[0]), False), (eos, True)]


# ---------------------------------------------------------------------------
# starvation-freedom under aging
# ---------------------------------------------------------------------------

def test_aging_bounds_low_priority_wait():
    eng, clock, _ = make_stub_engine(slots=1, aging=3.0)
    laggard = eng.submit(np.array([42]), max_new_tokens=1, priority=5)
    # continuous priority-0 flood: one fresh arrival per step, each
    # holding the slot for a decode step (n=2)
    flood = [[_req([i], n=2, priority=0)] for i in range(20)]
    run_scripted(eng, clock, flood, dt=1.0)
    assert laggard.done
    # aged to priority 0 at t=3, then FIFO order admits it ahead of the
    # flood's later arrivals -> bounded admission
    assert laggard.admit_step <= 6


def test_no_aging_starves_low_priority_under_flood():
    eng, clock, _ = make_stub_engine(slots=1, aging=None)
    laggard = eng.submit(np.array([42]), max_new_tokens=1, priority=5)
    flood = [[_req([i], n=2, priority=0)] for i in range(20)]
    for submits in flood:
        clock.advance(1.0)
        for kw in submits:
            eng.submit(**kw)
        eng.step()
    # while the flood lasts, the laggard never runs (the negative control
    # that test_aging_bounds_low_priority_wait is meaningful)
    assert laggard.admit_time is None


# ---------------------------------------------------------------------------
# events, stats, tiers
# ---------------------------------------------------------------------------

def test_event_stream_shape():
    eng, clock, _ = make_stub_engine(slots=1)
    r = eng.submit(np.array([3, 1]), max_new_tokens=3)
    _, events = run_scripted(eng, clock, [])
    mine = [e for e in events if e.request_id == r.id]
    assert [e.kind for e in mine] == ["admit", "token", "token", "token",
                                     "finish"]
    assert [e.token for e in mine if e.kind == "token"] == r.tokens
    assert all(e.tier == "a" for e in mine)
    steps = [e.step for e in mine]
    assert steps == sorted(steps)


def test_on_token_streaming_callback():
    eng, clock, _ = make_stub_engine(slots=1)
    seen = []
    r = eng.submit(np.array([5]), max_new_tokens=2,
                   on_token=lambda req, tok, done: seen.append((tok, done)))
    run_scripted(eng, clock, [])
    assert seen == [(r.tokens[0], False), (r.tokens[1], True)]


def test_tier_stats_accounting():
    eng, clock, _ = make_stub_engine(slots=2)
    eng.submit(np.array([1]), max_new_tokens=3)
    eng.submit(np.array([2]), max_new_tokens=3)
    stats = eng.run()
    s = stats["a"]
    assert isinstance(s, TierStats)
    assert s.n_finished == 2 and s.n_tokens == 6
    # both live the same 2 decode steps (prefill token is step-less)
    assert s.n_decode_steps == 2 and s.mean_occupancy == 2.0


def test_queue_and_prefill_depth_are_exact_request_seconds():
    """``queued_s`` (submitted, not admitted) and ``prefilling_s``
    (admitted, no first token) integrate on the engine's clock: a submit
    to an idle engine is charged nothing for the idle time before it, and
    a request waiting for a row is charged until its admission."""
    tiers = (TierSpec("a", priority=0), TierSpec("b", priority=1))
    eng, clock, _ = make_stub_engine(tiers=tiers, slots=1, prefill_chunk=2)

    def steps_at(*times):
        for t in times:
            clock.advance(t - clock.now())
            eng.step()

    clock.advance(2.0)
    a = eng.submit(np.arange(1, 6), tier="a", max_new_tokens=2)  # t=2
    clock.advance(1.0)
    b = eng.submit(np.array([7, 8]), tier="a", max_new_tokens=2)  # t=3
    # a: admitted at 3, chunks at 3, 4, 5 (first token at 5), retires at
    # 5; b waits for the row until 6, and its one chunk lands at once
    steps_at(3, 4, 5, 6)
    assert eng.idle
    clock.advance(2.0)
    c = eng.submit(np.array([1, 2, 3]), tier="b", max_new_tokens=2)  # t=8
    clock.advance(1.0)
    # a snapshot between events is current: c has queued for 1 s
    assert eng.lane_stats()["b"].queued_s == 1
    steps_at(10, 11)  # c: admitted at 10, first token at 11
    assert eng.idle and all(r.done for r in (a, b, c))

    st = eng.lane_stats()
    assert st["a"].queued_s == (3 - 2) + (6 - 3)
    assert st["a"].prefilling_s == (5 - 3) + 0
    assert st["b"].queued_s == 10 - 8
    assert st["b"].prefilling_s == 11 - 10
    # the same request-seconds, request by request
    assert [r.admit_time - r.arrival_time for r in (a, b, c)] == [1, 3, 2]


def test_lanes_are_independent_per_tier():
    tiers = (TierSpec("fast", priority=0), TierSpec("slow", priority=1))
    eng, clock, runners = make_stub_engine(tiers=tiers, slots=1)
    a = eng.submit(np.array([1, 2]), tier="fast", max_new_tokens=3)
    b = eng.submit(np.array([3, 4]), tier="slow", max_new_tokens=3)
    run_scripted(eng, clock, [])
    # one slot per lane, but the lanes never queue behind each other
    assert a.admit_step == b.admit_step == 1
    np.testing.assert_array_equal(a.result(), stub_reference([1, 2], 3))
    np.testing.assert_array_equal(b.result(), stub_reference([3, 4], 3))
    # each lane served its request on its own row 0 of its own page pool
    assert a.slot == b.slot == 0
    assert len(runners["fast"].prefill_calls) == 1
    assert len(runners["slow"].prefill_calls) == 1


def test_run_raises_structured_error_on_bound():
    eng, clock, _ = make_stub_engine(slots=1)
    eng.submit(np.array([1]), max_new_tokens=5)
    with pytest.raises(ServingError, match="did not drain"):
        eng.run(max_steps=1)


# ---------------------------------------------------------------------------
# request identity: duplicate ids, ndarray-safe equality, cache bounds
# ---------------------------------------------------------------------------

def test_duplicate_inflight_id_rejected_then_reusable():
    eng, clock, _ = make_stub_engine(slots=2)
    eng.submit(np.array([1, 2]), max_new_tokens=2, request_id="job")
    # same id while the first is still in flight: structured rejection
    # at submit time, not a silent second request shadowing the first
    with pytest.raises(ServingError, match="already in flight"):
        eng.submit(np.array([3]), max_new_tokens=1, request_id="job")
    run_scripted(eng, clock, [])
    # once finished the id is free again (retries reuse ticket ids)
    r2 = eng.submit(np.array([3]), max_new_tokens=1, request_id="job")
    run_scripted(eng, clock, [])
    assert r2.done


def test_failed_submit_does_not_leak_the_id():
    eng, clock, _ = make_stub_engine(slots=1, max_len=8)
    with pytest.raises(ServingError, match="max_len"):
        eng.submit(np.arange(6), max_new_tokens=5, request_id="job")
    # the rejected submit must not have registered "job" as in flight
    r = eng.submit(np.array([1]), max_new_tokens=1, request_id="job")
    run_scripted(eng, clock, [])
    assert r.done


def test_request_equality_is_identity_not_ndarray_compare():
    """Regression: dataclass __eq__ compared ndarray prompts elementwise,
    so Scheduler.pop_next's queue removal raised 'truth value of an
    array is ambiguous' whenever two queued requests had identical
    field values.  Requests now compare by identity (eq=False)."""
    a = Request(id="r0", prompt=np.array([1, 2]), max_new_tokens=1, tier="a")
    b = Request(id="r0", prompt=np.array([1, 2]), max_new_tokens=1, tier="a")
    assert a != b and a == a
    sched = Scheduler(("a",))
    sched.submit(a, now=0.0)
    sched.submit(b, now=0.0)
    assert sched.pop_next("a", now=0.0) is a   # list.remove by identity
    assert sched.pop_next("a", now=0.0) is b
    assert sched.pop_next("a", now=0.0) is None


def test_engine_drains_identical_content_requests():
    # end-to-end shape of the same regression: two indistinguishable
    # payloads queued behind one slot must both retire
    eng, clock, _ = make_stub_engine(slots=1)
    a = eng.submit(np.array([7, 7]), max_new_tokens=2)
    b = eng.submit(np.array([7, 7]), max_new_tokens=2)
    run_scripted(eng, clock, [])
    assert a.done and b.done
    np.testing.assert_array_equal(a.result(), b.result())


def test_prefill_cache_is_lru_bounded():
    # the compiled-prefill cache is keyed per CHUNK shape (not per prompt
    # length) and each entry owns a private jit wrapper, so eviction
    # actually drops the executable
    from repro.serving.engine import TransformerRunner
    from repro.session import Session

    sess = Session("qwen3-4b")
    runner = TransformerRunner(sess.config, sess.params, 1, 16,
                               page_size=4, prefill_cache_size=2)
    row = np.arange(runner.max_pages, dtype=np.int32)  # pages 0..max_pages-1
    for c in (2, 3, 4):               # third distinct chunk shape evicts LRU
        runner.prefill_chunk_step(np.arange(1, c + 1, dtype=np.int32),
                                  0, c, row)
    assert list(runner._prefill) == [("chunk", 3), ("chunk", 4)]
    # hit refreshes the 3-chunk; a new 5-chunk then evicts the 4-chunk
    runner.prefill_chunk_step(np.arange(1, 4, dtype=np.int32), 0, 3, row)
    runner.prefill_chunk_step(np.arange(1, 6, dtype=np.int32), 0, 5, row)
    assert list(runner._prefill) == [("chunk", 3), ("chunk", 5)]
    # prompts sharing a chunk shape share the executable: a length-7
    # prompt chunked at 5 reuses ("chunk", 5) and adds only the tail
    runner.prefill_chunk_step(np.arange(1, 8, dtype=np.int32), 0, 5, row)
    runner.prefill_chunk_step(np.arange(1, 8, dtype=np.int32), 5, 7, row)
    assert list(runner._prefill) == [("chunk", 5), ("chunk", 2)]
    with pytest.raises(ServingError, match="prefill_cache_size"):
        TransformerRunner(sess.config, sess.params, 1, 16,
                          prefill_cache_size=0)
