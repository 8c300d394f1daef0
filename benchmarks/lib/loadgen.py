"""The one general load generator: a traffic file of parameters in, a
fixed schedule of requests out, and the open loop that offers it.

The SHAPE of the traffic (how many requests, their lengths, the gaps
between their arrivals, which request follows which gap) comes from the
traffic file alone. `--seed` decides token ids and the phase of the cycle
at which a run starts: every seed offers the same periodic schedule, so a
percentile over a whole cycle repeats.
"""

from __future__ import annotations

import asyncio
import dataclasses
import math
import statistics
from typing import Any, Awaitable, Callable, Dict, Iterator, List, Optional

import numpy as np

from . import stats

_NORMAL = statistics.NormalDist()


def quantile_grid(spec: Dict[str, Any], n: int) -> List[float]:
    """n values at the quantiles (i + 0.5) / n of the distribution `spec`
    names, ascending: the distribution's shape with no draw in it."""
    qs = [(i + 0.5) / n for i in range(n)]
    dist = spec["dist"]
    if dist == "lognormal":
        mu, sigma = math.log(spec["median"]), spec["sigma"]
        vals = [math.exp(mu + sigma * _NORMAL.inv_cdf(q)) for q in qs]
    elif dist == "exponential":
        vals = [-math.log(1.0 - q) for q in qs]
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    lo, hi = spec.get("min", -math.inf), spec.get("max", math.inf)
    return [min(max(v, lo), hi) for v in vals]


def permutation(n: int, stride: int) -> List[int]:
    """The fixed permutation i -> i * stride mod n. A stride of 1 or -1
    mod n shuffles nothing: it leaves a sorted grid sorted (every short
    gap of a cycle in a row, one burst a cycle), so it is refused like
    one that is not coprime with n."""
    if math.gcd(stride, n) != 1:
        raise ValueError(f"stride {stride} is not coprime with {n}")
    if n > 2 and stride % n in (1, n - 1):
        raise ValueError(f"stride {stride} leaves a cycle of {n} in order")
    return [(i * stride) % n for i in range(n)]


def length_cycle(traffic: Dict[str, Any]) -> List[tuple]:
    """The cycle of (prompt_tokens, output_tokens) pairs, in sending
    order: both grids, paired and ordered by fixed permutations."""
    n = traffic["cycle"]
    prompts = [int(round(v)) for v in
               quantile_grid(traffic["prompt_tokens"], n)]
    outputs = [int(round(v)) for v in
               quantile_grid(traffic["output_tokens"], n)]
    pair = permutation(n, traffic["pair_stride"])
    order = permutation(n, traffic["order_stride"])
    pairs = [(prompts[i], outputs[pair[i]]) for i in range(n)]
    return [pairs[i] for i in order]


def arrival_gaps(traffic: Dict[str, Any]) -> List[float]:
    """One cycle of inter-arrival gaps in seconds: the arrival
    distribution's quantile grid in a fixed order, scaled so that a cycle
    lasts exactly cycle / rate_rps."""
    n = traffic["cycle"]
    grid = quantile_grid(traffic["arrival"], n)
    scale = n / traffic["rate_rps"] / sum(grid)
    order = permutation(n, traffic["gap_stride"])
    return [grid[i] * scale for i in order]


@dataclasses.dataclass
class Planned:
    index: int
    prompt_tokens: int
    output_tokens: int
    due_s: Optional[float] = None     # open loop: offset from window start


def open_schedule(traffic: Dict[str, Any], seed: int,
                  window_s: float) -> List[Planned]:
    """Requests due from `ramp_s` before the window to its end. The
    schedule is periodic: one cycle of (gap, pair) steps, each gap always
    followed by the same pair. The seed only decides at which step of the
    cycle the run starts, so over a whole cycle every seed offers the
    same sizes after the same gaps in the same order, from another
    phase."""
    gaps = arrival_gaps(traffic)
    cycle = length_cycle(traffic)
    rot = seed % len(cycle)
    out: List[Planned] = []
    t = -float(traffic["ramp_s"])
    i = 0
    while True:
        step = (i + rot) % len(cycle)
        t += gaps[step]
        if t >= window_s:
            return out
        p, o = cycle[step]
        out.append(Planned(index=i, prompt_tokens=p, output_tokens=o,
                           due_s=t))
        i += 1


def prompt_text(seed: int, index: int, n_tokens: int) -> str:
    """Seeded random printable ASCII that the byte tokenizer turns into
    n_tokens ids (one per character, plus BOS); no two prompts share a
    prefix beyond chance."""
    rng = np.random.default_rng([seed, index])
    chars = rng.integers(32, 127, max(n_tokens - 1, 0), dtype=np.uint8)
    return chars.tobytes().decode("ascii")


def packed_batches(job: Dict[str, Any], vocab_size: int, batch: int,
                   seq: int, seed: int) -> Iterator[np.ndarray]:
    """Training batches (batch, seq) int32: documents whose lengths walk
    a fixed heavy-tailed grid (rotated by the seed), each BOS plus ids
    drawn from a seeded Zipf over the vocabulary, concatenated and cut
    into full sequences, a long document running on into the next one.
    Every step holds batch * seq tokens whatever the seed."""
    n = job["cycle"]
    lengths = [int(round(v)) for v in quantile_grid(job["doc_tokens"], n)]
    order = permutation(n, job["order_stride"])
    rot = seed % n
    ranks = np.arange(1, vocab_size - 2, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(job["zipf_exponent"]))
    cdf /= cdf[-1]
    rng = np.random.default_rng([seed, 7])
    need, i = batch * seq, 0
    carry = np.zeros(0, np.int32)
    while True:
        parts, total = [carry], len(carry)
        while total < need:
            length = lengths[order[(i + rot) % n]]
            i += 1
            ids = 3 + np.searchsorted(cdf, rng.random(length - 1))
            parts.append(np.concatenate([[1], ids]).astype(np.int32))
            total += length
        flat = np.concatenate(parts)
        carry = flat[need:]
        yield flat[:need].reshape(batch, seq)


@dataclasses.dataclass
class Record:
    """What the client saw of one request; times are seconds from the
    window's start on the generator's clock."""
    plan: Planned
    sent_s: float
    token_times: List[float] = dataclasses.field(default_factory=list)
    token_ids: List[int] = dataclasses.field(default_factory=list)
    prompt_tokens_seen: int = 0
    finish_reason: Optional[str] = None
    done_s: Optional[float] = None
    error: Optional[str] = None


Send = Callable[[Record], Awaitable[None]]
Clock = Callable[[], float]


async def _guarded(send: Send, rec: Record) -> None:
    try:
        await send(rec)
    except asyncio.CancelledError:
        rec.error = rec.error or "unfinished when the grace ended"
        raise
    except Exception as e:          # the boundary: a failed request counts
        rec.error = f"{type(e).__name__}: {e}"


async def drive_open(send: Send, schedule: List[Planned], clock: Clock,
                     grace_s: float, window_s: float) -> List[Record]:
    """Send each request when it is due, whether or not earlier ones have
    finished; then wait up to grace_s past the window for the rest."""
    records: List[Record] = []
    tasks = []
    for plan in schedule:
        wait = plan.due_s - clock()
        if wait > 0:
            await asyncio.sleep(wait)
        rec = Record(plan=plan, sent_s=clock())
        records.append(rec)
        tasks.append(asyncio.create_task(_guarded(send, rec)))
    await _finish(tasks, window_s + grace_s - clock())
    return records


async def _finish(tasks: list, timeout: float) -> None:
    if not tasks:
        return
    _, pending = await asyncio.wait(tasks, timeout=max(timeout, 0.0))
    for t in pending:
        t.cancel()
    if pending:
        await asyncio.wait(pending, timeout=10.0)


def summarise(records: List[Record], window_s: float, vocab_size: int,
              start_s: float = 0.0) -> Dict[str, Any]:
    """Client-side end-to-end numbers over the requests of the window
    [start_s, start_s + window_s).

    `serve_tok_s` is over all the work of the window: every token
    streamed in it and the prompt of every request whose first token
    arrived there, over window_s. A request is measured if it was due
    inside the window, and its time to first token runs from when it was
    due, not from when it was sent. A measured request that failed, was
    refused, did not finish in the grace, ended for another reason than
    `length` or `stop`, or returned more tokens than asked or an id
    outside the vocabulary, counts in `failed` and contributes no
    latency. Of time to first token a few tens of requests carry a mean
    and a median, not a tail (a 95th percentile wants 200:
    `stats.BEYOND`); all of them ride along for the earlier lines."""
    end_s = start_s + window_s
    measured = [r for r in records if start_s <= r.plan.due_s < end_s]
    ttft, itl, late, bad = [], [], [], []
    for r in measured:
        why = r.error
        if why is None and r.finish_reason not in ("length", "stop"):
            why = f"finish_reason {r.finish_reason!r}"
        if why is None and len(r.token_ids) > r.plan.output_tokens:
            why = f"{len(r.token_ids)} tokens for {r.plan.output_tokens}"
        if why is None and not r.token_ids:
            why = "no token"
        if why is None and not all(0 <= t < vocab_size
                                   for t in r.token_ids):
            why = "token id outside the vocabulary"
        if why is not None:
            bad.append((r.plan.index, why))
            continue
        late.append((r.sent_s - r.plan.due_s) * 1e3)
        ttft.append((r.token_times[0] - r.plan.due_s) * 1e3)
        itl.extend((b - a) * 1e3 for a, b in
                   zip(r.token_times, r.token_times[1:]))
    # every token the server streamed inside the window, and every prompt
    # whose first token arrived inside it (its prefill ended there),
    # whichever request they belong to, ramp included
    tokens = 0
    for r in records:
        tokens += sum(1 for t in r.token_times if start_s <= t < end_s)
        if r.token_times and start_s <= r.token_times[0] < end_s:
            tokens += r.prompt_tokens_seen
    done = [r for r in records if r.error is None and r.done_s is not None
            and start_s <= r.done_s < end_s]
    out: Dict[str, Any] = {
        "attempted": len(measured), "failed": len(bad),
        "failures": bad[:10],
        "completed_in_window": len(done),
        "serve_tok_s": tokens / window_s,
        "ttft_n": len(ttft), "itl_n": len(itl),
        "finish_reasons": _count(r.finish_reason for r in measured),
        "ttft_mean_ms": statistics.fmean(ttft) if ttft else None,
        "ttft_max_ms": max(ttft) if ttft else None,
        "late_max_ms": max(late) if late else None,
    }
    for name, vals in (("ttft", ttft), ("itl", itl)):
        for p in (50, 95):
            out[f"{name}_p{p}_ms"] = (stats.percentile(vals, p)
                                      if vals else None)
    out["ttft_highest_supported_percentile"] = (
        stats.highest_supported_percentile(len(ttft)))
    # (prompt tokens, ms) of every measured request, in sending order
    out["ttft_by_request"] = [
        (r.plan.prompt_tokens, round((r.token_times[0] - r.plan.due_s)
                                     * 1e3, 1))
        for r in measured if r.token_times]
    return out


def _count(items) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for it in items:
        out[str(it)] = out.get(str(it), 0) + 1
    return out
