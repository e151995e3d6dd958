"""The benchmark's own open-loop load generator.

One general generator, driven by a traffic file (`traffic/<name>.json`,
``kind: "serve"``). It is the benchmark's, not the program's, so no PR that
claims a gain can change the clock its requests are timed by:

- arrivals are an OPEN loop: every request has a due time fixed before the
  run starts, and is sent then whether or not earlier ones have finished;
- a request's latency counts from the instant it was DUE, so a stall shows
  in the requests queued behind it; how late each one really left is
  reported beside it (`late_s`), so a starved generator is not read as a
  fast server;
- every seed gets the SAME multiset of inter-arrival gaps, prompt lengths,
  output lengths and continuation flags (the distributions' quantiles at
  (i+0.5)/N — no draw at all), in another order and another pairing, with
  other words. Two seeds therefore offer the same work, and a difference
  between two runs is the system's, not the draw's.

Sessions: every request keeps its session. The cell holds
``resident_sessions`` sessions on the device before the run (their carries
come from the seed; `serve_cell.preload`), and a request flagged as a
continuation is the next turn of one of them, chosen by the seed without
replacement: it names the session and sends only the new turn's words. Every
other request opens a session of its own.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np


@dataclasses.dataclass
class Arrival:
    idx: int
    due: float              # seconds from the window's opening; < 0 = pre-roll
    prompt_len: int
    new_tokens: int
    resident: int | None    # the resident session this turn continues
    word_seed: tuple


@dataclasses.dataclass
class Outcome:
    """What came back for one arrival. Times are `time.perf_counter()`."""
    arrival: Arrival
    due_at: float
    sent_at: float = math.nan
    ok: bool = False
    error: str | None = None       # "shed" | "timeout" | "failed: ..." | None
    first_token_at: float = math.nan
    token_at: tuple = ()
    done_at: float = math.nan
    tokens: tuple = ()
    prompt: tuple = ()             # the words really sent
    session_id: str | None = None
    continued: bool = False
    after: "Outcome | None" = None     # the turn a follow-up continues
    phases_ms: dict = dataclasses.field(default_factory=dict)

    @property
    def late_s(self) -> float:
        return self.sent_at - self.due_at


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int, hi: int):
    """The distribution's quantiles at (i+0.5)/n, clipped and rounded."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def _exponential_quantiles(n: int, mean: float):
    q = (np.arange(n) + 0.5) / n
    gaps = -mean * np.log1p(-q)
    return gaps * (mean * n / gaps.sum())   # they add up to n * mean


def _part(rng, traffic: dict, n: int, span: float, start: float):
    """``n`` arrivals over ``span`` seconds from ``start``: the quantile sets
    of gaps and lengths and a fixed count of continuation flags, each
    permuted by the seed. The gaps add up to ``span``, so the part ends
    where the next begins."""
    rate = n / span
    p, o = traffic["prompt_len"], traffic["output_len"]
    prompt = rng.permutation(_lognormal_quantiles(
        n, p["median"], p["sigma"], p["min"], p["max"]))
    output = rng.permutation(_lognormal_quantiles(
        n, o["median"], o["sigma"], o["min"], o["max"]))
    gaps = rng.permutation(_exponential_quantiles(n, 1.0 / rate))
    due = start + np.cumsum(gaps) - gaps       # the first is due at ``start``
    flags = np.zeros(n, bool)
    flags[:int(round(float(traffic["continue_share"]) * n))] = True
    return due, prompt, output, rng.permutation(flags)


def make_schedule(traffic: dict, seed: int, seconds: float, *,
                  preroll_s: float) -> list[Arrival]:
    """Every arrival of one run from the traffic file and the seed alone: a
    pre-roll over [-preroll_s, 0) and the window over [0, seconds), each
    with its own fixed multisets, so that the WINDOW holds the same number
    of requests, words and tokens whatever the seed."""
    rate = float(traffic["rate_per_s"])
    rng = np.random.default_rng([int(seed), 0x5EED])
    parts = []
    if preroll_s > 0:
        parts.append(_part(rng, traffic, max(int(round(rate * preroll_s)), 1),
                           preroll_s, -preroll_s))
    parts.append(_part(rng, traffic, max(int(round(rate * seconds)), 1),
                       seconds, 0.0))
    due, prompt, output, flags = (np.concatenate(x) for x in zip(*parts))
    if flags.sum() > int(traffic["resident_sessions"]):
        raise SystemExit(f"traffic: {flags.sum()} continuations of "
                         f"{traffic['resident_sessions']} resident sessions")
    residents = iter(rng.permutation(int(traffic["resident_sessions"])))
    return [Arrival(i, float(due[i]), int(prompt[i]), int(output[i]),
                    int(next(residents)) if flags[i] else None, (int(seed), i))
            for i in range(len(due))]


def words(arrival: Arrival, vocab: int, n: int) -> np.ndarray:
    """``n`` word ids for this arrival (ids 0 and 1 are <pad> and <unk>)."""
    rng = np.random.default_rng([*arrival.word_seed, 0xC0FFEE])
    return rng.integers(2, vocab, size=n).astype(np.int32)


class OpenLoop:
    """Send each arrival at its due time from ONE dispatcher thread; a pool
    of waiting threads holds the blocking calls (they sleep on the reply,
    so they cost the server's threads nothing but a wake-up)."""

    def __init__(self, arrivals: list[Arrival], send, *, workers: int):
        self.arrivals = arrivals
        self.send = send                   # send(outcome) fills it
        self.workers = workers
        self.outcomes: list[Outcome | None] = [None] * len(arrivals)

    def run(self, opens_at: float, *, drain_s: float) -> list[Outcome]:
        """Blocks until every arrival has been sent and has settled, or
        ``drain_s`` has passed since the last one was due."""
        pool = ThreadPoolExecutor(max_workers=self.workers,
                                  thread_name_prefix="bench-client")
        futures = []
        try:
            for a in self.arrivals:
                o = Outcome(a, due_at=opens_at + a.due)
                self.outcomes[a.idx] = o
                wait = o.due_at - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                futures.append(pool.submit(self._one, o))
            deadline = (opens_at + self.arrivals[-1].due + drain_s)
            for f in futures:
                try:
                    f.result(timeout=max(deadline - time.perf_counter(), 0.0))
                except TimeoutError:
                    pass                    # its outcome stays not-ok
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
        return [o for o in self.outcomes if o is not None]

    def _one(self, o: Outcome) -> None:
        o.sent_at = time.perf_counter()
        try:
            self.send(o)
        except Exception as e:  # the boundary: a failed request is a result
            o.ok = False
            o.error = o.error or f"failed: {type(e).__name__}: {e}"


def in_flight(outcomes: list[Outcome], at: float) -> int:
    """Requests due by ``at`` that had not settled by then."""
    return sum(1 for o in outcomes
               if o.due_at <= at and not (o.done_at <= at))
