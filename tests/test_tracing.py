"""utils/tracing: the one ``span()`` and its two sinks — Chrome trace JSON
through an installed Tracer (CLI --trace), the profiler's host plane under a
`jax.profiler` session (--profile-dir) — and what it does with neither."""

import json
import time

import jax
import pytest

from lstm_tensorspark_tpu.utils import Tracer, get_tracer, set_tracer, span


@pytest.fixture
def installed():
    """A Tracer installed as `--trace` installs it, for one test."""
    t = Tracer()
    set_tracer(t)
    yield t
    set_tracer(None)


def test_tracer_records_spans_and_saves(tmp_path, installed):
    t = installed
    with span("outer", phase="x"):
        time.sleep(0.01)
        with span("inner"):
            pass
    t.complete("marker", time.perf_counter(), time.perf_counter(), step=3)
    path = tmp_path / "trace.json"
    t.save(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    data = [e for e in events if e["ph"] != "M"]
    names = [e["name"] for e in data]
    assert set(names) == {"outer", "inner", "marker"}
    outer = next(e for e in events if e["name"] == "outer")
    inner = next(e for e in events if e["name"] == "inner")
    assert outer["ph"] == "X" and outer["dur"] >= 10_000  # >= 10ms in us
    assert outer["args"] == {"phase": "x"}
    # inner nested within outer's interval
    assert outer["ts"] <= inner["ts"] <= inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    # recording threads are named via thread_name METADATA events (full
    # tid, no 16-bit truncation that could fold two threads onto one row)
    import threading

    metas = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "thread_name"
               and e["tid"] == threading.get_ident()
               and e["args"]["name"] == threading.current_thread().name
               for e in metas)
    assert outer["tid"] == threading.get_ident()


def test_tracer_ring_buffer_caps_events(tmp_path):
    """Long serving runs must not grow the event list without bound: the
    ring keeps the NEWEST max_events and counts what it displaced."""
    t = Tracer(max_events=10)
    set_tracer(t)
    try:
        for i in range(25):
            with span(f"e{i}"):
                pass
    finally:
        set_tracer(None)
    assert t.dropped == 15
    path = tmp_path / "ring.json"
    t.save(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    kept = [e["name"] for e in events if e["name"].startswith("e")]
    assert kept == [f"e{i}" for i in range(15, 25)]  # newest survive
    drop = next(e for e in events if e["name"] == "tracer_dropped_events")
    assert drop["args"]["dropped"] == 15


def test_tracer_complete_and_tid_names(tmp_path):
    """complete(): spans from explicit perf_counter stamps on a synthetic
    named row — how serve emits per-request timelines after the fact."""
    t = Tracer()
    a = time.perf_counter()
    time.sleep(0.005)
    b = time.perf_counter()
    t.complete("queue", a, b, tid=42, request=7)
    t.set_tid_name(42, "request 7")
    path = tmp_path / "c.json"
    t.save(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    ev = next(e for e in events if e["name"] == "queue")
    assert ev["tid"] == 42 and ev["ph"] == "X"
    assert 4_000 <= ev["dur"] <= 500_000  # ~5ms in us (scheduler slack)
    assert ev["args"]["request"] == 7
    assert any(e["ph"] == "M" and e["tid"] == 42
               and e["args"]["name"] == "request 7" for e in events)


def test_module_helpers_noop_when_disabled(monkeypatch):
    """With neither sink a span returns without touching a Tracer; it
    still keeps its own two stamps."""
    set_tracer(None)
    assert get_tracer() is None

    def touched(*a, **k):
        raise AssertionError("a Tracer was touched with none installed")

    for method in ("complete", "_record"):
        monkeypatch.setattr(Tracer, method, touched)
    with span("nothing", rows=3) as sp:
        pass
    assert sp.start <= sp.end


def test_module_helpers_record_when_installed(tmp_path, installed):
    t = installed
    with span("phase"):
        with span("tick"):
            pass
    set_tracer(None)
    with span("after"):     # uninstalled: the Tracer sees no more
        pass
    path = tmp_path / "t.json"
    t.save(str(path))
    names = [e["name"] for e in json.loads(path.read_text())["traceEvents"]]
    assert names.count("phase") == 1 and names.count("tick") == 1
    assert "after" not in names


def test_cli_trace_end_to_end(tmp_path):
    from lstm_tensorspark_tpu.cli import main

    trace = tmp_path / "host_trace.json"
    rc = main([
        "--dataset", "ptb_char", "--hidden-units", "32", "--batch-size", "8",
        "--num-steps", "2", "--log-every", "1", "--backend", "single",
        "--trace", str(trace),
    ])
    assert rc == 0
    names = {e["name"] for e in json.loads(trace.read_text())["traceEvents"]}
    assert {"load_dataset", "setup", "train", "eval_final"} <= names
    assert get_tracer() is None  # uninstalled after the run


@pytest.mark.parametrize("known_device", [True, False])
def test_log_flops_records(tmp_path, monkeypatch, known_device):
    """--log-flops: throughput records carry model_tflops from the shared
    utils/flops formulas, and mfu against the device_kind's peak — or, on
    a device the peaks table does not hold (this CPU), no mfu and a note
    saying why."""
    import json

    import jax

    from lstm_tensorspark_tpu.cli import main
    from lstm_tensorspark_tpu.utils import flops
    from lstm_tensorspark_tpu.utils.flops import (
        TRAIN_FLOPS_MULTIPLIER, lm_fwd_flops_per_token,
    )

    kind = jax.devices()[0].device_kind
    assert kind not in flops.PEAK_BF16_TFLOPS
    if known_device:
        monkeypatch.setitem(flops.PEAK_BF16_TFLOPS, kind, 2.0)
    jsonl = tmp_path / "m.jsonl"
    rc = main([
        "--dataset", "ptb_char", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "8", "--seq-len", "16", "--num-steps", "4",
        "--log-every", "2", "--log-flops", "--backend", "single",
        "--jsonl", str(jsonl),
    ])
    assert rc == 0
    recs = [json.loads(l) for l in open(jsonl)]
    th = [r for r in recs if "tokens_per_sec" in r]
    assert th and all("model_tflops" in r for r in th)
    r = th[-1]
    # vocab size from the run's own start record (synthetic stand-in or a
    # real corpus — the test must match whatever the CLI loaded)
    V = next(rec["vocab"] for rec in recs if "vocab" in rec)
    fpt = TRAIN_FLOPS_MULTIPLIER * lm_fwd_flops_per_token(V, 16, 1)
    import numpy as np
    np.testing.assert_allclose(
        r["model_tflops"], r["tokens_per_sec"] * fpt / 1e12, rtol=1e-6
    )
    why = [rec["note"] for rec in recs
           if "no bf16 peak" in str(rec.get("note"))]
    if known_device:
        # single-chip run (--backend single): aggregate peak = one chip's
        np.testing.assert_allclose(
            r["mfu"], r["model_tflops"] / 2.0, atol=1e-4)
        assert not why
    else:
        assert not any("mfu" in rec for rec in th)
        assert len(why) == 1 and kind in why[0]


# ---- the second sink: the profiler's host plane ---------------------------


def _named(events, name):
    return [e for e in events if e["name"] == name]


def test_span_lands_in_profiler_trace_with_args(record_spans):
    def work():
        with span("engine:launch", program="window_fn", batch_bucket=4):
            with span("engine:fetch", rows=3, k=4):
                pass

    events = record_spans(work)
    (launch,) = _named(events, "engine:launch")
    (fetch,) = _named(events, "engine:fetch")
    assert launch["args"] == {"program": "window_fn", "batch_bucket": 4}
    assert fetch["args"] == {"rows": 3, "k": 4}
    assert launch["line"] == fetch["line"]
    assert launch["start"] <= fetch["start"] <= fetch["end"] <= launch["end"]


def test_one_span_call_feeds_both_sinks(record_spans, tmp_path, installed):
    """The same `span()` call, Tracer installed and profiler session on:
    the Chrome event `--trace` wrote before, and the profiler's event."""
    def work():
        with span("serve:decode_dispatch", rows=2, k=4) as sp:
            pass
        return sp

    events = record_spans(work)
    sp = record_spans.result
    (on_plane,) = _named(events, "serve:decode_dispatch")
    assert on_plane["args"] == {"rows": 2, "k": 4}
    path = tmp_path / "both.json"
    installed.save(str(path))
    (chrome,) = [e for e in json.loads(path.read_text())["traceEvents"]
                 if e["name"] == "serve:decode_dispatch"]
    assert chrome["ph"] == "X" and chrome["args"] == {"rows": 2, "k": 4}
    # one pair of stamps for both: the Chrome event is the span's own
    assert chrome["dur"] == pytest.approx((sp.end - sp.start) * 1e6)


@pytest.fixture(scope="module")
def train_loop_events(record_spans):
    """A tiny `train_loop` (6 calls of K=2 steps, a record every 3 calls,
    the anomaly watchdog on, an eval and a checkpoint) under a profiler
    session."""
    import jax.numpy as jnp

    from lstm_tensorspark_tpu.train.loop import train_loop

    class State:
        step = jnp.int32(0)
        params = None

    @jax.jit
    def step(batch):
        return {"loss": batch.sum(), "grad_norm": batch.max(),
                "anomalous": jnp.int32(0)}

    calls = []

    def train_step(state, batch):
        return state, step(batch)

    def run():
        return train_loop(
            State(), train_step, (jnp.ones((4,)) * i for i in range(100)),
            num_steps=6, log_every=3, steps_per_call=2, anomaly_limit=5,
            logger=type("L", (), {"log": staticmethod(calls.append)}),
            eval_fn=lambda params: {"eval_loss": 1.0}, eval_every=6,
            checkpoint_fn=calls.append, checkpoint_every=6)

    return record_spans(run), calls


@pytest.mark.parametrize("name,count", [
    ("train:feed", 7),        # six batches and the pull the budget ends on
    ("train:dispatch", 6),
    ("train:sync", 8),        # the watchdog's six and the two records'
    ("train:log", 2),
    ("train:eval", 1),
    ("train:checkpoint", 1),
])
def test_train_loop_leaves_its_spans(train_loop_events, name, count):
    events, _ = train_loop_events
    found = _named(events, name)
    assert len(found) == count
    assert len({e["line"] for e in found}) == 1
    if name == "train:dispatch":
        assert all(e["args"] == {"steps": 2} for e in found)


def test_train_spans_follow_the_loops_order(train_loop_events):
    events, calls = train_loop_events
    names = [e["name"] for e in events if e["name"].startswith("train:")]
    # an iteration without a record, and the last one with everything
    # (before the pull on which the budget ends)
    assert names[:3] == ["train:feed", "train:dispatch", "train:sync"]
    assert names[-8:] == ["train:feed", "train:dispatch", "train:sync",
                          "train:sync", "train:log", "train:eval",
                          "train:checkpoint", "train:feed"]
    assert sum("steps_per_sec" in c for c in calls if isinstance(c, dict)) == 2


def test_cli_profile_dir_records_spans_without_python_tracer(tmp_path, record_spans):
    """`--profile-dir`: the trace holds the loop's spans and the CLI's
    coarse ones on the host plane, and no event of the Python tracer."""
    from lstm_tensorspark_tpu.cli import main

    out = tmp_path / "profile"
    rc = main([
        "--dataset", "ptb_char", "--hidden-units", "16", "--batch-size", "8",
        "--seq-len", "16", "--num-steps", "4", "--log-every", "2",
        "--backend", "single", "--profile-dir", str(out),
    ])
    assert rc == 0
    events = record_spans.read(str(out))
    names = {e["name"] for e in events}
    assert {"train:feed", "train:dispatch", "train:sync", "train:log"} <= names
    # the Python tracer names its events "$<file>:<line> <function>"
    assert not [n for n in names if n.startswith("$")]
    assert len(_named(events, "train:dispatch")) == 4
