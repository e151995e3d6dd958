"""Serve-side telemetry: /metrics exposition over the real HTTP endpoint,
histogram summaries in /stats, per-request phase breakdowns, server-vs-
loadgen latency agreement, and the --trace request timelines.

One module-scoped server with its OWN MetricsRegistry (not the process
default) so every assertion reads exactly this stack's telemetry.
"""

import json
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from lstm_tensorspark_tpu.models import LMConfig, init_lm
from lstm_tensorspark_tpu.obs import (
    DEFAULT_LATENCY_BUCKETS,
    NULL_REGISTRY,
    MetricsRegistry,
    parse_exposition,
)
from lstm_tensorspark_tpu.serve import ServeEngine, ServeServer, run_loadgen
from lstm_tensorspark_tpu.utils import Tracer, set_tracer

_CFG = LMConfig(vocab_size=37, hidden_size=16, num_layers=2)


def _build(registry):
    params = init_lm(jax.random.PRNGKey(3), _CFG)
    engine = ServeEngine(
        params, _CFG, num_slots=8,
        prefill_buckets=(4, 8), batch_buckets=(1, 2, 4),
        registry=registry,
    )
    return ServeServer(engine, max_active=4, queue_size=16)


@pytest.fixture(scope="module")
def stack():
    reg = MetricsRegistry()
    server = _build(reg)
    server.start()
    yield reg, server
    server.stop()


def test_metrics_route_serves_valid_exposition(stack):
    from lstm_tensorspark_tpu.serve.server import make_http_server

    reg, server = stack
    httpd = make_http_server(server, port=0)
    host, port = httpd.server_address[:2]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        base = f"http://{host}:{port}"
        body = json.dumps({"prompt": [5, 1, 2], "max_new_tokens": 6,
                           "greedy": True}).encode()
        req = urllib.request.Request(
            base + "/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            ctype = r.headers["Content-Type"]
            text = r.read().decode()
        with urllib.request.urlopen(base + "/stats", timeout=30) as r:
            stats = json.loads(r.read())
    finally:
        httpd.shutdown()
        httpd.server_close()

    assert ctype.startswith("text/plain")
    fams = parse_exposition(text)  # raises on any format violation
    # the headline server-side distributions are all present as histograms
    for name in ("serve_ttft_seconds", "serve_itl_seconds",
                 "serve_queue_wait_seconds",
                 "serve_scheduler_iteration_seconds"):
        assert fams[name]["type"] == "histogram", name
        count = next(v for n, _, v in fams[name]["samples"]
                     if n == name + "_count")
        assert count >= 1, name
    # compile counters carry the phase label
    phases = {labels["phase"] for _, labels, _
              in fams["serve_compiles_total"]["samples"]}
    assert {"prefill", "decode"} <= phases
    assert fams["serve_requests_total"]["type"] == "counter"

    # the HTTP reply carries the per-request phase breakdown
    assert out["phases_ms"].get("queue_ms") is not None
    assert out["phases_ms"].get("prefill_ms", 0) > 0
    assert "decode_ms" in out["phases_ms"]

    # /stats (the JSON alias) now embeds histogram summaries
    ms = stats["metrics"]
    assert ms["serve_ttft_seconds"]["count"] >= 1
    assert "p50" in ms["serve_ttft_seconds"]
    assert "p99" in ms["serve_ttft_seconds"]


def _bucket_span(value_s: float) -> float:
    """Width of the DEFAULT_LATENCY_BUCKETS bucket containing value_s —
    the histogram's resolution at that point, hence the agreement bound."""
    lo = 0.0
    for hi in DEFAULT_LATENCY_BUCKETS:
        if value_s <= hi:
            return hi - lo
        lo = hi
    return float("inf")


def test_server_percentiles_agree_with_loadgen():
    """Server-side TTFT/ITL histograms and loadgen's sorted-sample
    percentiles observe the SAME timestamps, so they must agree to within
    the histogram's bucket resolution (the only quantization between
    them). Fresh registry + warmed server: the histograms then hold
    exactly this run's samples (no compile-inflated outliers)."""
    reg = MetricsRegistry()
    server = _build(reg)
    with server:
        server.warmup(prompt_lens=(4,))
        report = run_loadgen(server, vocab_size=_CFG.vocab_size, sessions=3,
                             requests_per_session=3, prompt_len=4,
                             max_new_tokens=6)
    assert report["failed"] == 0 and report["rejected"] == 0
    # every completed request's TTFT landed in the server histogram
    # (serve families carry a replica label; this stack is replica 0)
    h_ttft = reg.histogram("serve_ttft_seconds",
                           labelnames=("replica",)).labels(replica="0")
    assert h_ttft.snapshot()[2] == report["completed"]

    # loadgen embeds the server-side summaries next to its own numbers
    assert "server_histograms" in report
    assert report["server_histograms"]["serve_ttft_seconds"]["count"] >= 9

    for loadgen_key, name in (("p50_ttft_ms", "serve_ttft_seconds"),
                              ("p50_itl_ms", "serve_itl_seconds"),
                              ("p99_itl_ms", "serve_itl_seconds")):
        lg_s = report[loadgen_key] / 1e3
        q = 0.99 if loadgen_key.startswith("p99") else 0.5
        srv_s = reg.histogram(name, labelnames=("replica",)).labels(
            replica="0").quantile(q)
        tol = _bucket_span(lg_s) + 0.005  # bucket resolution + sched noise
        assert abs(srv_s - lg_s) <= tol, (loadgen_key, srv_s, lg_s, tol)


def test_trace_carries_request_timeline(tmp_path):
    """--trace on a serve run: every request gets a complete
    admit→queue→prefill→decode→readback timeline on its own named row."""
    server = _build(MetricsRegistry())
    tracer = Tracer()
    set_tracer(tracer)
    try:
        with server:
            reqs = [server.generate([1, 2, 3], max_new_tokens=6),
                    server.generate([4, 5], max_new_tokens=4)]
    finally:
        set_tracer(None)
    path = tmp_path / "serve_trace.json"
    tracer.save(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    for req in reqs:
        row = [e for e in events
               if e.get("args", {}).get("request") == req.id]
        names = {e["name"] for e in row}
        assert {"queue", "prefill"} <= names, names
        assert "decode" in names or "decode_window" in names, names
        # windowed decode also shows the fetch-blocked readback slice
        if "decode_window" in names:
            assert "readback" in names
        # one named row per request
        assert any(e["ph"] == "M" and e["args"]["name"] == f"request {req.id}"
                   for e in events)
        # and the blocking phases cover positive time
        total = req.phase_summary_ms()
        assert total.get("prefill_ms", 0) > 0


def test_null_registry_disables_serve_telemetry():
    """--telemetry off: the stack records nothing, /metrics says so, and
    requests still serve (the no-op instruments are the whole cost)."""
    server = _build(NULL_REGISTRY)
    with server:
        req = server.generate([1, 2, 3], max_new_tokens=4)
    assert len(req.tokens) == 4
    assert server.metrics_summary() == {}
    assert "disabled" in server.metrics_text()


def test_registry_counters_track_stats_counters():
    """Cache/prefix counters flow through the registry: the /metrics view
    and the legacy stats() ints advance together."""
    reg = MetricsRegistry()
    params = init_lm(jax.random.PRNGKey(3), _CFG)
    engine = ServeEngine(params, _CFG, num_slots=4,
                         prefill_buckets=(4, 8), batch_buckets=(1, 2),
                         prefix_cache=True, prefix_stride=2,
                         registry=reg)
    server = ServeServer(engine, max_active=2, queue_size=8)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    with server:
        server.generate(prompt, max_new_tokens=2)  # cold: miss + insert
        server.generate(prompt, max_new_tokens=2)  # hot: hit
    st = engine.prefix.stats()
    fam = reg.counter("serve_prefix_cache_events_total",
                      labelnames=("event",))
    assert fam.labels(event="hit").value == st["hits"] >= 1
    assert fam.labels(event="miss").value == st["misses"] >= 1
    assert fam.labels(event="insert").value == st["inserts"] >= 1
    swaps = reg.counter("serve_state_cache_swaps_total").value
    assert swaps == engine.cache.stats()["generation"] > 0


# ---- the program's spans on the profiler's host plane ----------------------


@pytest.fixture(scope="module")
def served_events(record_spans):
    """A few concurrent requests through `ServeServer.generate` on a warmed
    stack of its own, under a profiler session with the Python tracer off:
    the trace's host events, and the batcher's counters over the session."""
    server = _build(MetricsRegistry())
    with server:
        server.warmup(prompt_lens=(4, 8))
        batcher = server.batcher
        before = batcher.stats()

        def serve():
            threads = [threading.Thread(target=server.generate, args=(p,),
                                        kwargs={"max_new_tokens": n})
                       for p, n in (([1, 2, 3], 12), ([4, 5], 9),
                                    ([6, 7, 8, 9, 10], 7))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            server.generate([3, 1, 4], max_new_tokens=11)  # alone: windows
            # the reply is set free inside the iteration that delivered it:
            # let the scheduler close that iteration before the session ends
            replied = time.monotonic()
            while batcher.last_heartbeat <= replied:
                time.sleep(0.001)

        events = record_spans(serve)
        after = batcher.stats()
    return events, before, after


def _spans(events, name):
    return [e for e in events if e["name"] == name]


def _scheduler_line(events):
    """The thread of THIS stack's scheduler: the only one that dispatched
    (the module's shared `stack`, when a worker holds it, idles on a line
    of its own: iterations, waits and admits, and nothing else)."""
    (line,) = {e["line"] for e in _spans(events, "serve:prefill_dispatch")}
    return line


@pytest.mark.parametrize("name", [
    "serve:iteration", "serve:wait_for_work", "serve:admit",
    "serve:prefill_dispatch", "serve:decode_dispatch", "serve:deliver",
    "engine:pack", "engine:launch", "engine:fetch"])
def test_scheduler_thread_leaves_span(served_events, name):
    events, _, _ = served_events
    scheduler = _scheduler_line(events)
    assert [e for e in _spans(events, name) if e["line"] == scheduler], name
    if name not in ("serve:iteration", "serve:wait_for_work", "serve:admit"):
        assert {e["line"] for e in _spans(events, name)} == {scheduler}


def test_client_threads_open_no_span(served_events):
    """Every span of the program lies on a scheduler's line (one that holds
    `serve:iteration`): the four clients' threads, which only hand a
    request over and wait, leave none."""
    events, before, after = served_events
    assert after["submitted"] - before["submitted"] == 4
    schedulers = {e["line"] for e in _spans(events, "serve:iteration")}
    assert _scheduler_line(events) in schedulers
    assert {e["line"] for e in events if e["args"] is not None} <= schedulers


def test_dispatch_spans_agree_with_the_batchers_counters(served_events):
    events, before, after = served_events
    decodes = [e["args"] for e in _spans(events, "serve:decode_dispatch")]
    # every window the batcher counted is a span of that k (the per-token
    # path, k=1 and not pipelined, has no counter of its own)
    for k in {a["k"] for a in decodes} | set(after["windows_dispatched"]):
        counted = (after["windows_dispatched"].get(k, 0)
                   - before["windows_dispatched"].get(k, 0))
        windows = [a for a in decodes
                   if a["k"] == k and (k > 1 or a["pipelined"])]
        assert len(windows) == counted, k
    assert sum(a["pipelined"] for a in decodes) == (
        after["windows_pipelined"] - before["windows_pipelined"])
    # rows are live sessions: every generated token is one row of one step
    # of a decode dispatch, or a final prefill's first token
    prefills = [e["args"] for e in _spans(events, "serve:prefill_dispatch")]
    first_tokens = sum(a["rows"] for a in prefills if a["final"])
    generated = after["tokens_generated"] - before["tokens_generated"]
    assert generated == 12 + 9 + 7 + 11
    assert sum(a["rows"] * a["k"] for a in decodes) + first_tokens == generated
    assert first_tokens == 4
    # a deliver follows every fetch (a window's, a final prefill's first
    # token) and every prefill chunk
    chunks = sum(1 for a in prefills if not a["final"])
    assert len(_spans(events, "serve:deliver")) == (
        len(_spans(events, "engine:fetch")) + chunks)


def test_spans_nest_as_the_work_does(served_events):
    events, _, _ = served_events
    # the session starts and stops while the schedulers loop: a span whose
    # iteration was open at either end has no recorded parent, so look
    # between each line's first and last whole iteration
    whole = {}
    for e in _spans(events, "serve:iteration"):
        lo, hi = whole.get(e["line"], (e["start"], e["end"]))
        whole[e["line"]] = (min(lo, e["start"]), max(hi, e["end"]))
    events = [e for e in events if e["line"] in whole
              and whole[e["line"]][0] <= e["start"]
              and e["end"] <= whole[e["line"]][1]]

    def inside(inner, outer):
        return [i for i in _spans(events, inner) if any(
            o["line"] == i["line"] and o["start"] <= i["start"]
            and i["end"] <= o["end"] for o in _spans(events, outer))]

    launches = _spans(events, "engine:launch")
    programs = {e["args"]["program"] for e in launches}
    assert {"prefill_fn", "window_fn"} <= programs <= {
        "prefill_fn", "window_fn", "decode_fn"}
    in_decode = inside("engine:launch", "serve:decode_dispatch")
    in_prefill = inside("engine:launch", "serve:prefill_dispatch")
    assert {e["args"]["program"] for e in in_prefill} == {"prefill_fn"}
    assert {e["args"]["program"] for e in in_decode} == programs - {"prefill_fn"}
    assert len(in_decode) + len(in_prefill) == len(launches)
    # every dispatch, admit, deliver and engine span lies in an iteration;
    # the idle wait lies between iterations
    for name in ("serve:admit", "serve:prefill_dispatch", "serve:deliver",
                 "serve:decode_dispatch", "engine:pack", "engine:fetch"):
        assert len(inside(name, "serve:iteration")) == len(_spans(events, name)), name
    assert not inside("serve:wait_for_work", "serve:iteration")
    # the window's fetch names the program it waits for, its rows and k
    window_fetches = [e["args"] for e in _spans(events, "engine:fetch")
                      if e["args"]["program"] == "window_fn"]
    window_launches = [e for e in launches if e["args"]["program"] == "window_fn"]
    assert len(window_fetches) == len(window_launches)
    assert all(a["rows"] >= 1 and a["k"] >= 1 for a in window_fetches)


