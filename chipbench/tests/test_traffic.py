"""The generator gives every seed the same work in its own order."""
import numpy as np
import pytest

from chipbench import traffic


@pytest.mark.parametrize("mix", ["bulk-backlog", "chat-mixed"])
def test_every_seed_gets_the_same_schedule(mix):
    m = traffic.load_mix(mix)
    a = traffic.schedule(m, 1, 51, 1000)
    b = traffic.schedule(m, 2 ** 33 + 5, 51, 1000)
    key = lambda s: sorted((p.prompt.size, p.max_new, str(p.tier)) for p in s)
    assert len(a) == len(b)
    assert sorted(p.prompt.size for p in a) == sorted(p.prompt.size for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert sorted(p.tier for p in a) == sorted(p.tier for p in b)
    assert [(p.prompt.size, p.max_new, p.tier, p.due) for p in a] == \
        [(p.prompt.size, p.max_new, p.tier, p.due) for p in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    gaps = lambda s: sorted(np.round(np.diff([p.due for p in s]), 9))
    if m["arrivals"]["kind"] == "gamma":
        assert gaps(a) == gaps(b)
    assert key(a) == key(b)


def test_same_seed_same_inputs():
    m = traffic.load_mix("chat-mixed")
    a = traffic.schedule(m, 77, 51, 151936)
    b = traffic.schedule(m, 77, 51, 151936)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               for x, y in zip(a, b))


def test_gamma_rate_and_preroll():
    m = traffic.load_mix("chat-mixed")
    arr = m["arrivals"]
    s = traffic.schedule(m, 5, 51, 100)
    due = np.array([p.due for p in s])
    assert due[0] == pytest.approx(-arr["preroll_s"])
    gaps = np.diff(due)
    # the gaps are all but one of n quantiles scaled to a mean of 1/rate
    assert gaps.mean() == pytest.approx(1 / arr["rate_per_s"], rel=0.5)
    assert gaps.std() / gaps.mean() > 1.0          # bursty


@pytest.mark.parametrize("mix", ["bulk-backlog", "chat-mixed"])
def test_lengths_fit_the_engine(mix):
    m = traffic.load_mix(mix)
    for p in traffic.schedule(m, 3, 51, 100):
        assert m["prompt_tokens"]["min"] <= p.prompt.size <= m["prompt_tokens"]["max"]
        assert p.prompt.size + p.max_new - 1 <= m["engine"]["max_len"]
    s = traffic.schedule(m, 3, 51, 100)
    pages = traffic.page_counts(m, s)
    assert pages[0] >= 1 and pages[-1] <= m["engine"]["max_len"] // m["engine"]["page_size"]
    assert set(pages) == set(traffic.page_counts(m, traffic.schedule(m, 4, 51, 100)))
    chunks = traffic.chunk_lengths(m, s)
    assert chunks[-1] <= m["engine"]["prefill_chunk"]
    assert set(chunks) == set(traffic.chunk_lengths(m, traffic.schedule(m, 9, 51, 100)))
