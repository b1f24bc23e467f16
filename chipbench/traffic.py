"""The one traffic generator: a mix file of parameters -> a request schedule.

A mix (``traffic/<name>.json``) states the engine it is served by (tiers,
slots, ``max_len``, page size, prefill chunk, and ``prefill_cache``, the
compiled prefill shapes each lane keeps), its arrivals (``backlog``:
every request submitted before the window; ``gamma``: an open loop at a
fixed rate with gamma-distributed gaps of a stated coefficient of
variation, started ``preroll_s`` before the window) and lognormal prompt
and output lengths (median, sigma, clipped to ``[min, max]``).

Every seed gets the same schedule: lengths are the distribution's
quantiles at ``(i + 0.5) / n``, gaps are the gamma quantiles scaled to the
stated rate exactly, tier counts follow the shares by largest remainder,
and one fixed shuffle pairs them and sets their order.  The seed draws the
token ids (uniform over the vocabulary), so runs on different seeds do the
same work on different inputs: measured on the chip, a seed that also
reordered the requests moved tokens/s by more than twice what two runs of
one seed differ by.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

ROOT = Path(__file__).resolve().parent


@dataclasses.dataclass
class Planned:
    """One request of the schedule."""

    id: str
    due: float            # seconds from the window's start (< 0: pre-roll)
    tier: str
    prompt: np.ndarray    # (prompt_len,) int32
    max_new: int


def load_mix(name: str) -> dict:
    with open(ROOT / "traffic" / f"{name}.json") as f:
        return json.load(f)


def lognormal_quantiles(n: int, p: dict) -> list:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of a lognormal with
    the stated median and sigma, clipped to ``[min, max]``."""
    nd = NormalDist()
    out = []
    for i in range(n):
        x = p["median"] * math.exp(p["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), p["min"]), p["max"])))
    return out


def gamma_gaps(n: int, rate: float, cv: float) -> np.ndarray:
    """``n`` gaps at the quantiles ``(i + 0.5) / n`` of a gamma law with
    coefficient of variation ``cv``, scaled to a mean of exactly
    ``1 / rate``."""
    from scipy.special import gammaincinv

    shape = 1.0 / cv ** 2
    q = np.array([gammaincinv(shape, (i + 0.5) / n) for i in range(n)])
    return q / q.mean() / rate


def tier_counts(n: int, tiers: list) -> list:
    """Tier name per request by largest remainder over the shares."""
    shares = [t.get("share", 1.0) for t in tiers]
    total = sum(shares)
    raw = [n * s / total for s in shares]
    counts = [int(r) for r in raw]
    order = sorted(range(len(tiers)), key=lambda i: raw[i] - counts[i],
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return [t["name"] for t, c in zip(tiers, counts) for _ in range(c)]


def n_requests(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["kind"] == "backlog":
        return int(arr["requests"])
    if arr["kind"] == "gamma":
        return int(math.ceil(arr["rate_per_s"]
                             * (arr["preroll_s"] + seconds))) + 1
    raise ValueError(f"unknown arrivals kind {arr['kind']!r}")


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The seed's request schedule for one run of ``seconds``."""
    arr = mix["arrivals"]
    n = n_requests(mix, seconds)
    fixed = np.random.default_rng(0)
    order = fixed.permutation(n)
    prompts = np.asarray(lognormal_quantiles(n, mix["prompt_tokens"]))[order]
    outputs = fixed.permutation(lognormal_quantiles(n, mix["output_tokens"]))
    tiers = fixed.permutation(tier_counts(n, mix["engine"]["tiers"]))
    if arr["kind"] == "backlog":
        due = np.full(n, -math.inf)
    else:
        gaps = fixed.permutation(gamma_gaps(n - 1, arr["rate_per_s"],
                                            arr["cv"]))
        due = np.concatenate([[0.0], np.cumsum(gaps)]) - arr["preroll_s"]
    rng = np.random.default_rng(int(seed))
    max_len = mix["engine"]["max_len"]
    out = []
    for i in range(n):
        L, new = int(prompts[i]), int(outputs[i])
        if L + new - 1 > max_len:
            raise ValueError(f"mix draws prompt {L} + output {new} beyond "
                             f"max_len {max_len}")
        out.append(Planned(id=f"q{i}", due=float(due[i]), tier=str(tiers[i]),
                           prompt=rng.integers(0, vocab, L, dtype=np.int32),
                           max_new=new))
    return out


def chunk_lengths(mix: dict, planned: list) -> list:
    """The prefill-chunk lengths the engine runs for these requests: full
    chunks and each prompt's last, ragged one."""
    c = mix["engine"]["prefill_chunk"]
    return sorted({min(c, p.prompt.size) for p in planned}
                  | {(p.prompt.size - 1) % c + 1 for p in planned})


def page_counts(mix: dict, planned: list) -> list:
    """The numbers of pages these requests free when they retire (each
    holds pages for ``prompt + max_new - 1`` positions)."""
    ps = mix["engine"]["page_size"]
    return sorted({-(-(p.prompt.size + p.max_new - 1) // ps)
                   for p in planned})
