"""utils/tracing: span capture, Chrome trace JSON output, CLI --trace, and
no-op behavior when disabled."""

import json
import time

import pytest

from lstm_tensorspark_tpu.utils import Tracer, get_tracer, instant, set_tracer, span


def test_tracer_records_spans_and_saves(tmp_path):
    t = Tracer()
    with t.span("outer", phase="x"):
        time.sleep(0.01)
        with t.span("inner"):
            pass
    t.instant("marker", step=3)
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
    for i in range(25):
        t.instant(f"e{i}")
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


def test_module_helpers_noop_when_disabled():
    set_tracer(None)
    assert get_tracer() is None
    with span("nothing") as t:
        assert t is None
    instant("nothing")  # must not raise


def test_module_helpers_record_when_installed(tmp_path):
    t = Tracer()
    set_tracer(t)
    try:
        with span("phase"):
            instant("tick")
    finally:
        set_tracer(None)
    path = tmp_path / "t.json"
    t.save(str(path))
    names = [e["name"] for e in json.loads(path.read_text())["traceEvents"]]
    assert names.count("phase") == 1 and names.count("tick") == 1


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
