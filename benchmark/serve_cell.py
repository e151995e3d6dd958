"""A serving cell: the stack `cli serve` builds, warmed as `--http` warms
it, driven in this process through `ServeServer.generate` — the one call the
HTTP handler and `InprocessClient` both make, and the one that hands back
the request with its own first-token and per-token stamps, on the same clock
as the generator's. Only the process that holds the chip can trace it.

The run: build, warm every program the buckets can ask for, write the
traffic's resident sessions into the slot cache (`preload`), start, then one
open-loop pass over the schedule (`loadgen.py`): a pre-roll (set-up: the
queue reaches its steady state), the measured window, and a bounded drain;
then a few second turns of sessions the run opened (`follow_ups`), for the
judge. Counters are read at the window's two edges.
"""

from __future__ import annotations

import functools
import math
import os
import threading
import time

import numpy as np

import flops
import loadgen
import trace_reduce

#: a greedy pick may differ from the reference's by this share of max|logit|
#: (PERF.md §2: two bf16 roundings' worth; a wrong token is orders above it)
GREEDY_REL_TOL = 2.0 ** -6

# How every serving cell is run; what differs between cells is in its
# traffic file (rate, lengths, continuation share, resident sessions).
PREROLL_S = 5.0         # requests before the window: the queue reaches its steady state
DRAIN_S = 20.0          # the longest wait for stragglers after the last due time
CLIENT_THREADS = 128    # blocked calls the generator can hold
JUDGE_REQUESTS = 8      # completed requests judged against the reference
FOLLOW_UPS = 2          # of them: second turns sent after the drain
TRACE_SECONDS = 4.0     # of the window's middle, in a traced run
DECODE_PROGRAM = "window_fn"   # the decode window's program, as the trace names it
PRELOAD_CHUNK = 8192    # resident sessions written per `write_slots` call


def build(cell):
    """``(sampling, params, cfg, server)``: the stack as `cli serve` builds
    it from the configuration's flags, warmed as `--http` warms it."""
    from lstm_tensorspark_tpu import cli

    model, serve = cell.config["model"], cell.config["serve"]
    argv = ["--http", "--vocab-size", str(model["vocab_size"]),
            "--hidden-units", str(model["hidden_size"]),
            "--num-layers", str(model["num_layers"]),
            "--compute-dtype", model["compute_dtype"],
            *serve["flags"], "--seed", str(cell.seed)]
    args = cli.build_serve_parser().parse_args(argv)
    params, cfg, server = cli._build_serve_stack(args, 1)
    sampling = cli._serve_sampling(args)
    server.warmup(sampling, prompt_lens=tuple(server.engine.prefill_buckets))
    preload(cell, server)
    return sampling, params, cfg, server


def resident_carries(cell, first: int, n: int):
    """Carries (h, c), each [L, n, H] float32, of resident sessions
    ``first .. first+n``: a state an LSTM can be in (c in (-1, 1),
    h = o * tanh(c) with o in (0, 1)), made on the device from the seed and
    the sessions' numbers alone, so the judge can make any one again."""
    import jax
    import jax.numpy as jnp

    model = cell.config["model"]
    return _carries_fn(model["num_layers"], model["hidden_size"])(
        jax.random.PRNGKey(cell.seed), first + jnp.arange(n))


@functools.lru_cache(maxsize=None)
def _carries_fn(layers: int, hidden: int):
    import jax
    import jax.numpy as jnp

    def one(key, i):
        kc, ko = jax.random.split(jax.random.fold_in(key, i))
        c = jax.random.uniform(kc, (layers, hidden), minval=-1.0, maxval=1.0)
        return jax.random.uniform(ko, (layers, hidden)) * jnp.tanh(c), c

    return jax.jit(jax.vmap(one, in_axes=(None, 0), out_axes=1))


def preload(cell, server) -> None:
    """The working set: ``resident_sessions`` sessions registered with the
    state cache and their carries written into its slots, through the
    cache's own `acquire` and `write_slots`, before any request. The pool a
    deployment reserves is then a pool it holds, and a continuation finds
    its session where a deployment would: among the many that are idle."""
    cache = server.engine.cache
    n = int(cell.traffic["resident_sessions"])
    if n > cache.num_slots:
        raise SystemExit(f"{n} resident sessions, {cache.num_slots} slots")
    for first in range(0, n, PRELOAD_CHUNK):
        count = min(PRELOAD_CHUNK, n - first)
        slots = [cache.acquire(resident_id(i))[0]
                 for i in range(first, first + count)]
        cache.write_slots(np.asarray(slots), *resident_carries(cell, first, count))


def resident_id(i: int) -> str:
    return f"resident-{i}"


def make_send(cell, server, sampling, *, give_up_at):
    from lstm_tensorspark_tpu.serve.batcher import QueueFullError

    vocab = cell.config["model"]["vocab_size"]

    def send(o: loadgen.Outcome, session=None, prompt=None) -> None:
        a = o.arrival
        if a.resident is not None:
            session, o.continued = resident_id(a.resident), True
        if prompt is None:
            prompt = loadgen.words(a, vocab, a.prompt_len)
        o.prompt = tuple(int(t) for t in prompt)
        try:
            req = server.generate(
                prompt, max_new_tokens=a.new_tokens, sampling=sampling,
                session_id=session, keep_session=True,
                timeout=max(give_up_at() - time.perf_counter(), 0.05))
        except QueueFullError:
            o.error = "shed"
            return
        except TimeoutError:
            o.error = "timeout"
            return
        o.first_token_at = req.t_first_token
        o.token_at = tuple(req.t_tokens)
        o.done_at = req.t_done
        o.tokens = tuple(int(t) for t in req.tokens)
        o.session_id = req.session_id
        o.phases_ms = req.phase_summary_ms()
        o.ok = len(o.tokens) == a.new_tokens
        if not o.ok:
            o.error = f"failed: {len(o.tokens)} of {a.new_tokens} tokens"

    return send


def follow_ups(cell, outcomes, send, n: int) -> list:
    """After the drain: the second turn of ``n`` sessions this run opened,
    one at a time. Only the new words are sent (the reply's last token,
    which the carry has not consumed, then the turn's), so the judge holds
    the carry that decoding left in the slot to the reference."""
    rng = np.random.default_rng([cell.seed, 0xF0110])
    opened = [o for o in outcomes if o.ok and not o.continued]
    out = []
    for k in rng.permutation(len(opened))[:n]:
        first = opened[k]
        idx = len(outcomes) + len(out)      # beyond every arrival's
        a = loadgen.Arrival(idx, math.nan, 8, 8, None, (cell.seed, idx))
        o = loadgen.Outcome(a, due_at=time.perf_counter())
        o.sent_at, o.continued, o.after = o.due_at, True, first
        send(o, first.session_id, np.concatenate(
            [[first.tokens[-1]], loadgen.words(a, cell.config["model"]["vocab_size"], 7)]))
        out.append(o)
    return out


class Watch(threading.Thread):
    """Tells a pause of the whole process from a stall of the program. It
    wakes every 20 ms and notes how late it woke and the CPU time the
    process had used by then: when the host stops the process, this thread
    is late too, the generator with it, and no CPU time passes; when a
    thread of the process holds the interpreter, CPU time passes. Every 250
    ms it reads the batcher's token counter; the first time that has stood
    still for a second with requests waiting, it notes where every thread
    of the program is (a few frames each)."""

    def __init__(self, server, waiting):
        super().__init__(name="bench-watch", daemon=True)
        self.server, self.waiting = server, waiting
        self.late: list[tuple[float, float, float]] = []   # (at, s late, CPU s)
        self.stall: dict | None = None
        self._stop_event = threading.Event()

    def run(self):
        period, last_poll, tokens, since = 0.02, 0.0, None, 0.0
        while not self._stop_event.is_set():
            due = time.perf_counter() + period
            time.sleep(period)
            now = time.perf_counter()
            self.late.append((now, now - due, time.process_time()))
            if now - last_poll < 0.25:
                continue
            last_poll = now
            seen = sum(r.batcher.stats()["tokens_generated"]
                       for r in self.server.replicas)
            if seen != tokens or not self.waiting():
                tokens, since = seen, now
            elif now - since >= 1.0 and self.stall is None:
                self.stall = {"at": since, "noticed_at": now, "threads": _stacks()}

    def stop(self):
        self._stop_event.set()
        self.join()

    def worst_late(self, lo: float, hi: float) -> tuple[float, float]:
        """The latest wake inside [lo, hi): seconds late, and the CPU
        seconds the whole process used between the wake before it and it."""
        inside = [i for i, (at, _, _) in enumerate(self.late) if lo <= at < hi and i]
        if not inside:
            return math.nan, math.nan
        i = max(inside, key=lambda i: self.late[i][1])
        return self.late[i][1], self.late[i][2] - self.late[i - 1][2]


def _stacks() -> dict:
    import sys
    import traceback

    names = {t.ident: t.name for t in threading.enumerate()}
    return {names.get(i, str(i)): [f"{f.filename.rsplit('/', 1)[-1]}:{f.lineno} {f.name}"
                                   for f in traceback.extract_stack(frame)[-6:]]
            for i, frame in sys._current_frames().items()
            if not names.get(i, "").startswith("bench-")}


def counters(server) -> dict:
    s = server.stats()
    b = s["batcher"]
    return {"compiles": sum(s["compiles"].values()),
            "tokens_generated": b["tokens_generated"],
            "windows_dispatched": {int(k): v for k, v in
                                   b["windows_dispatched"].items()},
            "prefill_chunks_dispatched": b["prefill_chunks_dispatched"],
            "completed": b["completed"], "rejected": b["rejected"],
            "failed": b["failed"], "max_active": b["max_active"],
            "decode_kernel": s["decode_kernel"]}


def windows_between(c0: dict, c1: dict) -> dict:
    """Decode windows dispatched between two counter readings, by size k."""
    return {k: n - c0["windows_dispatched"].get(k, 0)
            for k, n in c1["windows_dispatched"].items()}


def window_numbers(outcomes, opens_at: float, closes_at: float) -> dict:
    """What one window's outcomes say: the requests due in it, the completed
    ones, seconds to first token from the due time (misses as the window's
    length), every inter-token gap, and the tokens delivered inside it."""
    window = [o for o in outcomes if opens_at <= o.due_at < closes_at]
    ok = [o for o in window if o.ok]
    return {"window": window, "ok": ok,
            "ttft": ttft_samples(window, closes_at - opens_at),
            "gaps": [b - a for o in ok for a, b in zip(o.token_at, o.token_at[1:])],
            "tokens": sum(1 for o in outcomes for t in o.token_at
                          if opens_at <= t < closes_at)}


def judge_sample(cell, params, outcomes, followed, n: int) -> dict:
    """A seeded sample of completed requests, teacher-forced through the
    plain reference: new sessions from zero carries, next turns of resident
    sessions from the carries `preload` wrote, and every follow-up on its
    whole conversation."""
    from reference import lstm_lm as reference

    rng = np.random.default_rng([cell.seed, 0x10D6E])

    def some(pool, k):
        return [pool[i] for i in rng.permutation(len(pool))[:k]]

    picked = [o for o in followed if o.ok]
    rest = n - len(followed)
    picked += some([o for o in outcomes if o.ok and o.continued], rest // 2)
    picked += some([o for o in outcomes if o.ok and not o.continued],
                   n - len(picked))
    exact = ties = 0
    worst, bad = 0.0, []
    for o in picked:
        consumed, carries = list(o.prompt), None
        if o.arrival.resident is not None:
            h, c = resident_carries(cell, o.arrival.resident, 1)
            carries = [(h[layer, 0], c[layer, 0]) for layer in range(h.shape[0])]
        elif o.continued:               # a follow-up
            consumed = list(o.after.prompt) + list(o.after.tokens[:-1]) + consumed
        ok, e, t, gap = reference.judge_greedy(
            params, consumed, o.tokens, rel_tol=GREEDY_REL_TOL, carries=carries)
        exact, ties, worst = exact + e, ties + t, max(worst, gap)
        if not ok:
            bad.append(o.arrival.idx)
    return {"requests": len(picked),
            "resident": sum(o.arrival.resident is not None for o in picked),
            "follow_ups": len(followed),
            "tokens_exact": exact, "tokens_tied": ties,
            "worst_gap_share": worst, "tolerance_share": GREEDY_REL_TOL,
            "wrong_requests": bad,
            "ok": len(picked) == n and not bad}


def run(cell) -> dict:
    import jax

    traffic = cell.traffic
    sampling, params, cfg, server = build(cell)
    arrivals = loadgen.make_schedule(traffic, cell.seed, cell.seconds,
                                     preroll_s=PREROLL_S)
    edges: dict = {}
    trace_dir = os.path.join(cell.workdir, "profile") if cell.trace else None

    with server:
        opens_at = time.perf_counter() + PREROLL_S + 0.25
        closes_at = opens_at + cell.seconds
        send = make_send(cell, server, sampling,
                         give_up_at=lambda: closes_at + DRAIN_S)
        loop = loadgen.OpenLoop(arrivals, send, workers=CLIENT_THREADS)
        watch = Watch(server, lambda: loadgen.in_flight(
            [o for o in loop.outcomes if o is not None], time.perf_counter()) > 0)

        def edge(name, at, then=None):
            def fire():
                edges[name] = (time.perf_counter(), counters(server))
                if then:
                    then()
            t = threading.Timer(max(at - time.perf_counter(), 0), fire)
            t.daemon = True
            t.start()
            return t

        timers = [edge("open", opens_at), edge("close", closes_at)]
        if trace_dir:
            t_len = min(TRACE_SECONDS, cell.seconds / 2)
            t_at = opens_at + (cell.seconds - t_len) / 2

            def begin():
                # the Python tracer hooks every call of every thread: it
                # would slow the scheduler it is there to watch
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                with jax.profiler.TraceAnnotation("bench:window_open"):
                    edges["trace_open"] = time.perf_counter()
                    edges["trace_c0"] = counters(server)

            def end():
                with jax.profiler.TraceAnnotation("bench:window_close"):
                    edges["trace_close"] = time.perf_counter()
                    edges["trace_c1"] = counters(server)
                jax.profiler.stop_trace()

            timers += [edge("t0", t_at, begin), edge("t1", t_at + t_len, end)]
        watch.start()
        outcomes = loop.run(opens_at, drain_s=DRAIN_S)
        watch.stop()
        for t in timers:
            t.join()
        followed = follow_ups(cell, outcomes, make_send(
            cell, server, sampling,
            give_up_at=lambda: time.perf_counter() + DRAIN_S), FOLLOW_UPS)
        after = counters(server)
        slots = server.engine.cache.stats()

    opened, c0 = edges["open"]
    closed, c1 = edges["close"]
    n = window_numbers(outcomes, opens_at, closes_at)
    window, ok, ttft, gaps, tokens_in_window = (
        n["window"], n["ok"], n["ttft"], n["gaps"], n["tokens"])
    mid = opens_at + cell.seconds / 2
    judged = judge_sample(cell, params, outcomes, followed, JUDGE_REQUESTS)
    compiles = c1["compiles"] - c0["compiles"]
    correct = {"reference": judged["ok"], "no_compile_in_window": compiles == 0,
               "some_completed": bool(ok),
               "residents_held": slots["evictions"] == 0
               and slots["live_sessions"] >= int(traffic["resident_sessions"])}
    stamps = sorted(t for o in outcomes for t in o.token_at
                    if opens_at <= t < closes_at)
    pause_s, pause_cpu_s = watch.worst_late(opens_at, closes_at)
    result = {
        "correct": all(correct.values()), "checks": correct,
        "attempted": len(window), "failed": len(window) - len(ok),
        "setup_s": opens_at - cell.t0,
        "end_to_end": {
            "ttft_p95_ms": 1e3 * flops.percentile(ttft, 95) if ttft else math.nan,
            "ttft_p50_ms": 1e3 * flops.percentile(ttft, 50) if ttft else math.nan,
            "itl_p95_ms": 1e3 * flops.percentile(gaps, 95) if gaps else math.nan,
            "serve_tokens_per_s": tokens_in_window / cell.seconds,
        },
        "samples": {
            "ttft_p95_ms": f"{len(ttft)} requests due in the window "
                           f"({len(ttft) - len(ok)} failed/shed/unfinished); "
                           f"p50 {1e3 * flops.percentile(ttft, 50):.3f} ms, "
                           f"p95 {1e3 * flops.percentile(ttft, 95):.3f} ms, "
                           f"max {1e3 * max(ttft):.3f} ms" if ttft else "none",
            "itl_p95_ms": f"{len(gaps)} gaps of {len(ok)} completed requests",
            "serve_tokens_per_s": f"{tokens_in_window} tokens delivered in "
                                  f"{cell.seconds} s",
            "errors": _count(o.error for o in window if not o.ok),
            "continued": sum(o.continued for o in window),
            "in_flight_mid": loadgen.in_flight(outcomes, mid),
            "in_flight_end": loadgen.in_flight(outcomes, closes_at),
            "edge_lateness_s": [opened - opens_at, closed - closes_at],
            "reference": judged, "slots": slots, "stall": watch.stall,
            "decode_kernel": after["decode_kernel"],
            "preroll_requests": sum(o.due_at < opens_at for o in outcomes),
        },
        # what the per-layer readers may read
        "window_s": cell.seconds, "outcomes": window, "ttft_s": ttft,
        "counters": (c0, c1),
        "host_pause_max_s": pause_s,
        "delivery_gap_max_s": max((b - a for a, b in zip(
            [opens_at, *stamps], [*stamps, closes_at])), default=math.nan),
        "param_bytes": max(x.dtype.itemsize for x in jax.tree.leaves(
            server.engine._residents[server.engine.model_id]["params"])),
        "trace": None, "trace_window": None, "sync_mark": None,
    }
    result["samples"].update(
        host_pause_max_s=pause_s, host_pause_cpu_s=pause_cpu_s,
        delivery_gap_max_s=result["delivery_gap_max_s"])
    if trace_dir:
        path = trace_reduce.find_xplane(trace_dir)
        if path:
            result["trace"] = trace_reduce.load(path)
            result["trace_counters"] = (edges["trace_c0"], edges["trace_c1"])
    return result


def ttft_samples(window, seconds: float) -> list[float]:
    """Seconds from the instant each request was DUE to its first token. A
    request that failed, was shed or did not finish waited "for ever": it
    enters the tail as the window's length, so it can only worsen it."""
    return [(o.first_token_at - o.due_at) if o.ok else seconds for o in window]


def _count(items) -> dict:
    out: dict = {}
    for x in items:
        key = (x or "?").split(":")[0]
        out[key] = out.get(key, 0) + 1
    return out
