"""In-process driver of a `ServeServer` for the drills and the tests.

Not a benchmark: the system's speed is measured by `BENCHMARK.json` +
`benchmark/` (whose own generator, `benchmark/loadgen.py`, is independent
of this package). `run_loadgen` is what `tools/chaos_serve.py`'s
burst-shedding drill and the serving tests push traffic through when
they need many concurrent requests with one report at the end.

Two modes:

- **closed-loop**: N session threads, each issuing its requests
  back-to-back through the in-process client — models N always-busy
  clients;
- **open-loop**: requests arrive on a schedule (`arrival_offsets`: fixed
  rate, bursts, a sine-modulated rate or a replayed trace) regardless of
  completion, exposing queueing delay and backpressure (429s are counted,
  and retried only with ``retry_max``).

The report carries request latency p50/p99/mean, time-to-first-token
p50/p99, inter-token latency p50/p99/max (pooled over every token gap of
every completed request), aggregate tokens/sec and requests/sec, the
per-class slices (``priority_frac``, ``deadline_s``, ``retry_max``),
per-replica served counts and the router's requeue/rejection deltas.
``shared_prefix_len`` makes every prompt share its first N tokens;
``inject_prompt_len`` submits one cold long-prompt request mid-run and
reports it separately. Phases are wrapped in `utils.tracing` spans.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..resilience.backoff import backoff_delay
from ..utils import span
from .batcher import CLASSES, DeadlineExceededError, QueueFullError
from .engine import GREEDY, SamplingParams
from .server import InprocessClient, ServeServer
from .state_cache import PREFIX_STATS_CONFIG_KEYS


def arrival_offsets(
    n: int,
    *,
    rate: float | None = None,
    arrival: str = "fixed",
    arrival_times: list[float] | None = None,
    burst_n: int = 8,
    burst_gap_s: float = 0.5,
    sine_period_s: float = 2.0,
    sine_amp: float = 0.8,
) -> list[float]:
    """Arrival schedule for ``n`` open-loop requests: seconds-from-start
    offsets, non-decreasing.

    - ``arrival_times`` (trace replay): explicit
      offsets, one request each. A trace shorter than ``n`` LOOPS —
      replayed shifted by its span plus one mean gap, so the recorded
      diurnal/burst shape repeats instead of truncating the workload.
    - ``"fixed"``: the classic constant rate (needs ``rate`` > 0).
    - ``"burst"``: groups of ``burst_n`` simultaneous arrivals every
      ``burst_gap_s`` seconds — with ``rate`` set, requests inside a
      burst are spaced ``1/rate`` apart instead of landing at once.
    - ``"sine"``: a diurnal-shaped rate ``rate * (1 + amp *
      sin(2*pi*t/period))``, generated by stepping ``dt = 1/rate(t)``
      (amp < 1 keeps the instantaneous rate positive).
    """
    if n < 1:
        return []
    if arrival_times is not None:
        trace = [float(t) for t in arrival_times]
        if not trace or any(t < 0 for t in trace):
            raise ValueError("arrival trace needs >= 1 offset, all >= 0")
        if trace != sorted(trace):
            raise ValueError("arrival trace offsets must be sorted")
        gap = trace[-1] / max(len(trace) - 1, 1)
        period = trace[-1] + max(gap, 1e-3)
        return [trace[i % len(trace)] + (i // len(trace)) * period
                for i in range(n)]
    if arrival == "fixed":
        if not rate or rate <= 0:
            raise ValueError("fixed arrivals need rate > 0 (req/s)")
        return [i / rate for i in range(n)]
    if arrival == "burst":
        if burst_n < 1 or burst_gap_s <= 0:
            raise ValueError(
                f"burst arrivals need burst_n >= 1 and burst_gap_s > 0, "
                f"got {burst_n}, {burst_gap_s}")
        intra = 1.0 / rate if rate and rate > 0 else 0.0
        if (burst_n - 1) * intra > burst_gap_s:
            # a burst spilling past the next burst's start silently
            # degenerates into a continuous stream while the report
            # still claims distinct bursts — refuse, don't mislead
            raise ValueError(
                f"burst arrivals overlap: {burst_n} requests spaced "
                f"1/rate = {intra:g}s span past the {burst_gap_s}s "
                "burst gap — raise rate/burst_gap_s or lower burst_n")
        return [(i // burst_n) * burst_gap_s + (i % burst_n) * intra
                for i in range(n)]
    if arrival == "sine":
        if not rate or rate <= 0:
            raise ValueError("sine arrivals need rate > 0 (mean req/s)")
        if not 0.0 <= sine_amp < 1.0 or sine_period_s <= 0:
            raise ValueError(
                f"sine arrivals need 0 <= amp < 1 and period > 0, got "
                f"{sine_amp}, {sine_period_s}")
        import math

        out, t = [], 0.0
        for _ in range(n):
            out.append(t)
            r = rate * (1.0 + sine_amp
                        * math.sin(2.0 * math.pi * t / sine_period_s))
            t += 1.0 / max(r, 1e-6)
        return out
    raise ValueError(
        f"arrival must be 'fixed', 'burst' or 'sine', got {arrival!r}")


def _percentile(sorted_vals: list[float], pct: float) -> float:
    if not sorted_vals:
        return float("nan")
    idx = int(round(pct / 100.0 * (len(sorted_vals) - 1)))
    return sorted_vals[min(max(idx, 0), len(sorted_vals) - 1)]


def _random_prompts(n: int, prompt_len: int, vocab_size: int, seed: int,
                    shared_prefix_len: int = 0):
    """``shared_prefix_len > 0`` models the shared-system-prompt workload:
    every prompt starts with the SAME random prefix of that length and
    differs only in its suffix — the prefix cache's target case."""
    if shared_prefix_len >= prompt_len:
        raise ValueError(
            f"shared_prefix_len {shared_prefix_len} must be < prompt_len "
            f"{prompt_len} (each prompt needs a unique suffix)")
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, vocab_size, size=shared_prefix_len)
    return [
        np.concatenate([
            shared,
            rng.randint(0, vocab_size, size=prompt_len - shared_prefix_len),
        ]).astype(np.int32)
        for _ in range(n)
    ]


def _per_replica(results: list[dict]) -> dict:
    """Completed-request / token counts by the replica that served them
    (``Request.replica``, stamped by the router) — the scaling gate's
    routed-request evidence. Single-replica runs report one bucket."""
    out: dict[str, dict] = {}
    for r in results:
        if r.get("replica") is None:
            continue
        d = out.setdefault(str(r["replica"]), {"completed": 0, "tokens": 0})
        d["completed"] += 1
        d["tokens"] += r["tokens"]
    return out


def _class_report(recs: list[dict], shed: int, retried: int,
                  timeouts: int, wall: float) -> dict:
    """Per-admission-class slice of a run: completion/shed/retry/timeout
    counts plus TTFT and latency percentiles — the evidence the
    burst-shedding gate compares (priority p99 TTFT holds its SLO while
    best_effort sheds). ``tokens_per_sec`` is the
    class's ACCEPTED-token throughput over the run's wall clock, so a
    speculative win (or a shedding loss) shows up per SLO class instead
    of being averaged away in the aggregate rate."""
    ttft = sorted(r["ttft_s"] for r in recs if r["ttft_s"] is not None)
    lat = sorted(r["latency_s"] for r in recs)
    tokens = sum(r["tokens"] for r in recs)

    def pct(vals, p):
        # None (JSON null), never NaN: the classes section is ALWAYS
        # present, so a zero-traffic class in a default single-class run
        # must not make every --json report unparseable to strict
        # RFC-8259 consumers (json.dump writes bare NaN)
        if not vals:
            return None
        return round(_percentile(vals, p) * 1e3, 3)

    return {
        "completed": len(recs),
        "shed": shed,
        "retried": retried,
        "timeouts": timeouts,
        "tokens": tokens,
        "tokens_per_sec": round(tokens / wall, 3) if wall > 0 else None,
        "p50_ttft_ms": pct(ttft, 50),
        "p99_ttft_ms": pct(ttft, 99),
        "p50_latency_ms": pct(lat, 50),
        "p99_latency_ms": pct(lat, 99),
    }


def prefix_totals(server: ServeServer) -> dict | None:
    """Prefix-store stats summed across every replica's store (entries
    are replica-local; the workload-level hit rate is the sum's). Config
    keys (:data:`~.state_cache.PREFIX_STATS_CONFIG_KEYS` — shared with
    the server's heartbeat fan-in) keep replica 0's value; the ONE
    aggregation used by loadgen reports and the CLI's engine section, so
    the two can never drift. Works unchanged for both store modes —
    the flat-int stats contract is exactly what makes the sum legal."""
    totals = None
    for rep in server.replicas:
        if rep.engine.prefix is None:
            continue
        st = rep.engine.prefix.stats()
        if totals is None:
            totals = dict(st)
            continue
        for k, v in st.items():
            if k not in PREFIX_STATS_CONFIG_KEYS:
                totals[k] += v
    return totals


def _report(results: list[dict], rejected: int, failed: int, wall_s: float,
            mode: str, sessions: int) -> dict:
    lat = sorted(r["latency_s"] for r in results)
    ttft = sorted(r["ttft_s"] for r in results if r["ttft_s"] is not None)
    # inter-token latency: pooled token-arrival gaps across all requests
    # (a request with T tokens contributes T-1 gaps; TTFT is reported
    # separately and is NOT a gap here)
    itl = sorted(g for r in results for g in r.get("itl_s", ()))
    tokens = sum(r["tokens"] for r in results)
    return {
        "mode": mode,
        "sessions": sessions,
        "requests": len(results) + rejected + failed,
        "completed": len(results),
        "rejected": rejected,
        "failed": failed,
        "wall_s": round(wall_s, 4),
        "p50_latency_ms": round(_percentile(lat, 50) * 1e3, 3),
        "p99_latency_ms": round(_percentile(lat, 99) * 1e3, 3),
        "mean_latency_ms": round(
            (sum(lat) / len(lat) if lat else float("nan")) * 1e3, 3),
        "p50_ttft_ms": round(_percentile(ttft, 50) * 1e3, 3),
        "p99_ttft_ms": round(_percentile(ttft, 99) * 1e3, 3),
        "p50_itl_ms": round(_percentile(itl, 50) * 1e3, 3),
        "p99_itl_ms": round(_percentile(itl, 99) * 1e3, 3),
        "max_itl_ms": round(max(itl) * 1e3, 3) if itl else float("nan"),
        "tokens_generated": tokens,
        "tokens_per_sec": round(tokens / wall_s, 2) if wall_s > 0 else 0.0,
        "requests_per_sec": round(len(results) / wall_s, 2)
        if wall_s > 0 else 0.0,
    }


def run_loadgen(
    server: ServeServer,
    *,
    vocab_size: int,
    sessions: int = 8,
    requests_per_session: int = 4,
    prompt_len: int = 8,
    max_new_tokens: int = 16,
    sampling: SamplingParams = GREEDY,
    mode: str = "closed",
    rate: float | None = None,
    seed: int = 0,
    timeout: float = 300.0,
    shared_prefix_len: int = 0,
    inject_prompt_len: int = 0,
    inject_delay_s: float = 0.25,
    priority_frac: float = 1.0,
    deadline_s: float | None = None,
    retry_max: int = 0,
    retry_base_s: float = 0.05,
    retry_cap_s: float = 2.0,
    arrival: str = "fixed",
    arrival_times: list[float] | None = None,
    burst_n: int = 8,
    burst_gap_s: float = 0.5,
    sine_period_s: float = 2.0,
    sine_amp: float = 0.8,
) -> dict:
    """Drive a started :class:`ServeServer`; returns the report dict.

    ``shared_prefix_len``: prompts share their first N tokens (the
    prefix-cache workload). ``inject_prompt_len > 0``: one extra request
    with a prompt of that length is submitted ``inject_delay_s`` seconds
    into the run — the head-of-line-blocking probe (does a max-bucket
    prefill mid-run stall everyone else's ITL?); it is reported under
    ``"injected"`` and EXCLUDED from the pooled latency stats.

    ``priority_frac``: share of traffic submitted as the "priority"
    admission class; the rest goes "best_effort" (interleaved, so a
    burst mixes both). ``deadline_s`` rides on every request.
    ``retry_max > 0``: a 429 shed is retried up to that many times,
    sleeping the server's ``Retry-After`` hint floored by the SHARED
    capped exponential backoff + jitter (resilience/backoff.py — the
    supervisor's curve, one implementation), both capped at
    ``retry_cap_s``; per-class shed/retried/timeout counts land in the
    report's ``classes`` section."""
    if mode not in ("closed", "open"):
        raise ValueError(f"mode must be 'closed' or 'open', got {mode!r}")
    if not 0.0 <= priority_frac <= 1.0:
        raise ValueError(
            f"priority_frac must be in [0, 1], got {priority_frac}")
    if (arrival != "fixed" or arrival_times is not None) and mode != "open":
        raise ValueError(
            "arrival shaping (trace / burst / sine) is an OPEN-loop "
            "feature: closed-loop arrivals are completion-driven")
    client = InprocessClient(server)
    total = sessions * requests_per_session
    prompts = _random_prompts(total, prompt_len, vocab_size, seed,
                              shared_prefix_len)
    n_priority = int(round(sessions * priority_frac))
    results: list[dict] = []
    rejected = [0]
    failed = [0]
    shed = {c: 0 for c in CLASSES}
    retried = {c: 0 for c in CLASSES}
    timeouts = {c: 0 for c in CLASSES}
    lock = threading.Lock()
    prefix_before = prefix_totals(server)
    router_before = server.router.stats()

    def one_request(prompt, klass: str = "priority") -> None:
        t0 = time.perf_counter()
        attempt = 0
        while True:
            try:
                req = server.generate(
                    prompt, max_new_tokens=max_new_tokens,
                    sampling=sampling, timeout=timeout, klass=klass,
                    deadline_s=deadline_s,
                )
                break
            except QueueFullError as e:
                if attempt >= retry_max:
                    # shed for good: an honest 429 the client accepted
                    with lock:
                        rejected[0] += 1
                        shed[klass] += 1
                    return
                attempt += 1
                with lock:
                    retried[klass] += 1
                # honor Retry-After, floored by the shared backoff curve
                # (jittered so a shed burst doesn't re-arrive in
                # lockstep), both capped at retry_cap_s
                hint = getattr(e, "retry_after_s", None) or 0.0
                time.sleep(min(
                    max(hint, backoff_delay(retry_base_s, attempt,
                                            cap=retry_cap_s)),
                    retry_cap_s))
            except DeadlineExceededError:
                # server-side expiry: honest partial output, counted as
                # a timeout for this class — not a failure
                with lock:
                    timeouts[klass] += 1
                return
            except Exception:
                # a timeout or scheduler-side failure must not kill the
                # worker thread (its remaining requests would silently
                # vanish from the report) — count it and keep going
                with lock:
                    failed[0] += 1
                return
        rec = {
            "latency_s": time.perf_counter() - t0,
            "ttft_s": (req.t_first_token - req.t_submit)
            if req.t_first_token and req.t_submit else None,
            "tokens": len(req.tokens),
            "itl_s": req.itl_gaps(),
            "replica": req.replica,
            "klass": klass,
        }
        with lock:
            results.append(rec)

    injected: dict = {}

    def inject() -> None:
        time.sleep(inject_delay_s)
        # a fresh random prompt (distinct seed → shares nothing): a cold
        # max-bucket prefill landing in the middle of steady-state decode
        prompt = _random_prompts(1, inject_prompt_len, vocab_size,
                                 seed + 7919)[0]
        t0 = time.perf_counter()
        try:
            # use_prefix=False: the probe must neither perturb the shared
            # cache (stride-stop inserts would evict real entries) nor
            # skew the report's prefix_cache deltas with its cold miss
            req = server.generate(prompt, max_new_tokens=max_new_tokens,
                                  sampling=sampling, use_prefix=False,
                                  timeout=timeout)
        except Exception as e:
            injected["error"] = f"{type(e).__name__}: {e}"
            return
        injected.update({
            "prompt_len": inject_prompt_len,
            "latency_ms": round((time.perf_counter() - t0) * 1e3, 3),
            "ttft_ms": round((req.t_first_token - req.t_submit) * 1e3, 3)
            if req.t_first_token and req.t_submit else None,
            "tokens": len(req.tokens),
        })

    with span("loadgen", mode=mode, sessions=sessions, total=total):
        t_start = time.perf_counter()
        inject_thread = None
        if inject_prompt_len > 0:
            inject_thread = threading.Thread(target=inject, daemon=True)
            inject_thread.start()
        if mode == "closed":
            def worker(wid: int) -> None:
                # per-session class: the first n_priority sessions are
                # priority, the rest best-effort
                klass = "priority" if wid < n_priority else "best_effort"
                for r in range(requests_per_session):
                    one_request(prompts[wid * requests_per_session + r],
                                klass)

            threads = [
                threading.Thread(target=worker, args=(i,), daemon=True)
                for i in range(sessions)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        else:  # open loop: arrivals follow the schedule, not completion
            offsets = arrival_offsets(
                len(prompts), rate=rate, arrival=arrival,
                arrival_times=arrival_times, burst_n=burst_n,
                burst_gap_s=burst_gap_s, sine_period_s=sine_period_s,
                sine_amp=sine_amp)
            threads = []
            for i, prompt in enumerate(prompts):
                # interleaved class pattern (period = sessions): a burst
                # carries both classes throughout, not one then the other
                klass = ("priority"
                         if (i % max(sessions, 1)) < n_priority
                         else "best_effort")
                target = t_start + offsets[i]
                delay = target - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                t = threading.Thread(
                    target=one_request, args=(prompt, klass), daemon=True
                )
                t.start()
                threads.append(t)
            for t in threads:
                t.join()
        # wall covers the POOLED workload only — joining the probe after
        # would charge its sleep+request tail to tokens_per_sec while its
        # tokens are excluded from results
        wall = time.perf_counter() - t_start
        if inject_thread is not None:
            inject_thread.join()
    report = _report(results, rejected[0], failed[0], wall, mode, sessions)
    # per-class accounting (shed/retried/timeout + TTFT percentiles):
    # always present so report consumers have a stable shape; a
    # single-class run simply shows zeros for the other class
    report["timeouts"] = sum(timeouts.values())
    report["requests"] += report["timeouts"]
    report["priority_frac"] = priority_frac
    if deadline_s is not None:
        report["deadline_s"] = deadline_s
    if retry_max:
        report["retry_max"] = retry_max
    report["classes"] = {
        c: _class_report([r for r in results if r.get("klass") == c],
                         shed[c], retried[c], timeouts[c], wall)
        for c in CLASSES
    }
    if rate:
        report["offered_rate_rps"] = rate
    if mode == "open":
        report["arrival"] = ("trace" if arrival_times is not None
                             else arrival)
        if arrival_times is not None:
            report["arrival_trace_len"] = len(arrival_times)
        elif arrival == "burst":
            report["burst_n"] = burst_n
            report["burst_gap_s"] = burst_gap_s
        elif arrival == "sine":
            report["sine_period_s"] = sine_period_s
            report["sine_amp"] = sine_amp
    report["prompt_len"] = prompt_len
    report["shared_prefix_len"] = shared_prefix_len
    if inject_prompt_len > 0:
        report["injected"] = injected
    # per-replica routing evidence: completed/token counts by serving
    # replica (from the requests) + the router's routed/requeue deltas
    report["replicas"] = _per_replica(results)
    ra, rb = server.router.stats(), router_before
    report["router"] = {
        "replicas": ra["replicas"],
        "live": ra["live"],
        "routed": {k: ra["routed"][k] - rb["routed"].get(k, 0)
                   for k in ra["routed"]},
        "rejected": ra["rejected"] - rb["rejected"],
        "shed_by_class": {
            c: ra["shed_by_class"][c] - rb.get("shed_by_class", {}).get(c, 0)
            for c in ra.get("shed_by_class", {})
        },
        "requeued": ra["requeued"] - rb["requeued"],
        "rerouted": ra["rerouted"] - rb.get("rerouted", 0),
        "failed_on_death": ra["failed_on_death"] - rb["failed_on_death"],
        "migrated_sessions":
            ra["migrated_sessions"] - rb["migrated_sessions"],
    }
    if prefix_before is not None:
        after = prefix_totals(server)
        hits = after["hits"] - prefix_before["hits"]
        misses = after["misses"] - prefix_before["misses"]
        report["prefix_cache"] = {
            "hits": hits,
            "misses": misses,
            "hit_rate": round(hits / (hits + misses), 4)
            if hits + misses else 0.0,
            "inserts": after["inserts"] - prefix_before["inserts"],
            "entries": after["entries"],
            "invalidated": after["invalidated"] - prefix_before["invalidated"],
        }
    # server-side latency distributions (obs/ registry histograms) next to
    # the loadgen-side percentiles above, so the two views are directly
    # diffable. NOTE: the registry is CUMULATIVE over the server's life.
    summary = server.metrics_summary()
    hists = {k: summary[k] for k in ("serve_ttft_seconds",
                                     "serve_itl_seconds",
                                     "serve_queue_wait_seconds")
             if isinstance(summary.get(k), dict)}
    if hists:
        report["server_histograms"] = hists
    return report
