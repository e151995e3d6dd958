"""`program_spans.py`: the attribution of idle time to spans on hand-made
intervals; the readers built on it give nothing on a trace without program
spans (the recorded TPU trace: what the parent commit leaves), and in a
rehearsal on the CPU, which has no device plane, the four that read spans
alone give a number and the three that need the chip's idle time none."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import program_spans as ps
import run
import trace_reduce as tr
from program_spans import NO_SPAN, ProgramSpans, Span
from trace_reduce import ChipTrace, Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
RECORDED = os.path.join(HERE, "data", "tiny_tpu.xplane.pb")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    NEW = [m for m in json.load(f)["per_layer"] if m["source"] == "program_span"
           and os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
           and m["name"] != "queue_wait_p95_ms"]
SPANS_ALONE = {"sched_host_ms_per_dispatch", "readback_wait_ms",
               "decode_rows_mean", "train_host_ms_per_dispatch"}
NEED_THE_CHIP = {"idle_attributed_share.serve", "no_work_idle_share.serve",
                 "idle_attributed_share.train"}
SCHED, OTHER = ("/host:CPU", 0), ("/host:CPU", 1)


def span(name, start, end, line=SCHED, **args):
    return Span(name, line, start, end, args)


def test_the_seven_metrics_are_the_ones_this_file_knows():
    assert {m["name"] for m in NEW} == SPANS_ALONE | NEED_THE_CHIP


def test_a_gap_is_split_over_the_two_spans_it_overlaps():
    spans = [span("serve:deliver", 0.0, 2.0), span("serve:admit", 2.0, 5.0)]
    assert ps.attribute([(1.0, 4.0)], spans) == pytest.approx(
        {"serve:admit": 2.0, "serve:deliver": 1.0})


def test_a_gap_under_no_span_is_named_so():
    spans = [span("serve:admit", 0.0, 1.0), span("serve:admit", 6.0, 7.0)]
    assert ps.attribute([(2.0, 3.0), (5.5, 6.5)], spans) == pytest.approx(
        {NO_SPAN: 1.5, "serve:admit": 0.5})


def test_nested_spans_give_the_innermost():
    spans = [span("serve:iteration", 0.0, 10.0),
             span("serve:decode_dispatch", 1.0, 6.0),
             span("engine:pack", 2.0, 3.0), span("engine:launch", 3.0, 5.0),
             span("serve:deliver", 7.0, 8.0)]
    assert ps.innermost(spans) == [
        (0.0, 1.0, "serve:iteration"), (1.0, 2.0, "serve:decode_dispatch"),
        (2.0, 3.0, "engine:pack"), (3.0, 5.0, "engine:launch"),
        (5.0, 6.0, "serve:decode_dispatch"), (6.0, 7.0, "serve:iteration"),
        (7.0, 8.0, "serve:deliver"), (8.0, 10.0, "serve:iteration")]
    assert ps.attribute([(0.5, 2.5), (4.0, 9.0)], spans) == pytest.approx(
        {"serve:iteration": 0.5 + 1.0 + 1.0, "serve:decode_dispatch": 1.0 + 1.0,
         "engine:pack": 0.5, "engine:launch": 1.0, "serve:deliver": 1.0})


def chip_and_spans():
    """A chip busy in [0, 2] and [5, 7] of the window [0, 8], a scheduler
    that waited for work in [2.5, 4] of an otherwise covered stretch, and
    another replica's scheduler, asleep on its own thread all the while."""
    chip = ChipTrace(0, [Event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0.0, 2.0),
                         Event("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q)", 5.0, 7.0)],
                     [Event("jit_window_fn(1)", 0.0, 2.0), Event("jit_window_fn(1)", 5.0, 7.0)])
    spans = [span("serve:iteration", 0.0, 2.5), span("serve:wait_for_work", 2.5, 4.0),
             span("serve:iteration", 4.5, 8.0), span("engine:launch", 4.6, 4.8, program="window_fn"),
             span("serve:wait_for_work", 0.0, 8.0, line=OTHER)]
    return chip, spans


def test_spans_of_another_thread_are_ignored():
    chip, spans = chip_and_spans()
    result = {"trace": Trace([chip], []), "trace_window": (0.0, 8.0), "samples": {},
              "program_spans": ProgramSpans(spans)}
    cell = types.SimpleNamespace(chips=1, workdir="/nonexistent")
    split = ps.idle_by_span(result, cell, ps.SCHEDULER_ANCHOR)
    # idle: [2, 5] and [7, 8]; the other thread's wait covers all of it
    # and gets none of it
    assert split == pytest.approx({"serve:wait_for_work": 1.5, "serve:iteration": 0.5 + 0.3 + 1.0,
                                   "engine:launch": 0.2, NO_SPAN: 0.5})
    assert result["samples"]["idle_by_span"] is split
    assert ps.idle_attributed_share(result, cell, ps.SCHEDULER_ANCHOR) == pytest.approx(100 * 3.5 / 4.0)
    assert run.read_layer_metric("no_work_idle_share.serve", result, cell) == pytest.approx(100 * 1.5 / 4.0)
    assert run.read_layer_metric("sched_host_ms_per_dispatch", result, cell) == pytest.approx(1e3 * 6.0)


def test_the_clock_check_says_what_was_found_and_whether_it_was_taken_out():
    found = ps.clock_check({"lo": -2.4e-3, "hi": -2.1e-3, "offset": -2.25e-3, "programs": 180})
    assert found == pytest.approx({"device_ahead_lo": -2.4, "device_ahead_hi": -2.1,
                                   "device_ahead": -2.25, "programs": 180, "corrected": 1})
    # bounds that contradict each other, and a trace without the runtime's events
    assert ps.clock_check({"lo": 1e-3, "hi": -1e-3, "programs": 2}) == pytest.approx(
        {"device_ahead_lo": 1.0, "device_ahead_hi": -1.0, "programs": 2, "corrected": 0})
    assert ps.clock_check({}) == {"programs": 0, "corrected": 0}


def test_without_an_offset_the_split_is_as_the_trace_has_it():
    chip, spans = chip_and_spans()
    cell = types.SimpleNamespace(chips=1, workdir="/nonexistent")
    splits = []
    for clock in ({}, {"offset": 0.0}):
        result = {"trace": Trace([chip], []), "trace_window": (0.0, 8.0), "samples": {},
                  "program_spans": ProgramSpans(spans, clock)}
        assert ProgramSpans(spans, clock).device_ahead == clock.get("offset")
        splits.append(ps.idle_by_span(result, cell, ps.SCHEDULER_ANCHOR))
    assert splits[0] == pytest.approx(splits[1])


def test_the_chips_lead_is_bracketed_by_what_cannot_happen():
    """Run 1 starts 0.3 s "before" it was enqueued, and its completion is
    handled 0.5 s after its end: the chip's clock is behind the host's by
    0.3 to 0.5 s. Events of runs the chip never showed say nothing."""
    runs = {1: (10.0, 12.0), 2: (13.0, 14.0), 3: (20.0, 21.0)}
    clock = ps.clock_offset(runs, {1: 10.3, 2: 13.2, 9: 0.0}, {1: 12.5, 2: 14.6})
    assert clock["hi"] == pytest.approx(-0.3) and clock["lo"] == pytest.approx(-0.5)
    assert clock["offset"] == pytest.approx(-0.4) and clock["programs"] == 2
    # bounds that contradict each other: no offset; nothing to pair: nothing
    assert "offset" not in ps.clock_offset(runs, {1: 10.3}, {1: 12.1})
    assert ps.clock_offset(runs, {}, {1: 12.1}) == {}


def test_the_recorded_trace_brackets_its_own_clock():
    """`data/tiny_tpu.xplane.pb`: no program span, and the runtime's events
    put the chip's clock 1.2 to 1.7 ms behind the host's."""
    program = ps.read(RECORDED)
    assert program.spans == [] and program.clock["programs"] == 4
    assert -1.8e-3 < program.clock["lo"] <= program.device_ahead <= program.clock["hi"] < -1.1e-3


def test_idle_intervals_are_moved_onto_the_hosts_clock():
    """A chip 0.5 s ahead of the host: its idle [2, 5] is the host's
    [1.5, 4.5]."""
    chip, spans = chip_and_spans()
    result = {"trace": Trace([chip], []), "trace_window": (0.0, 8.0), "samples": {},
              "program_spans": ProgramSpans(spans, {"offset": 0.5})}
    split = ps.idle_by_span(result, types.SimpleNamespace(chips=1, workdir="/nonexistent"),
                            ps.SCHEDULER_ANCHOR)
    # [1.5, 4.5]: iteration 1.0, wait 1.5, no span 0.5; [6.5, 7.5]: iteration 1.0
    assert split == pytest.approx({"serve:iteration": 2.0, "serve:wait_for_work": 1.5, NO_SPAN: 0.5})


@pytest.mark.parametrize("metric", [m["name"] for m in NEW])
def test_reader_gives_nothing_without_program_spans(metric, tmp_path):
    """The recorded TPU trace holds the benchmark's marks and a device
    plane and no program span: what a traced run of the parent leaves."""
    os.makedirs(tmp_path / "profile")
    shutil.copy(RECORDED, tmp_path / "profile" / "t.xplane.pb")
    trace = tr.load(RECORDED)
    marks = [m.start for m in trace.marks]
    result = {"trace": trace, "trace_window": (min(marks), max(marks)), "samples": {},
              "sync_mark": "bench:log_record", "steps": 4}
    cell = types.SimpleNamespace(chips=1, workdir=str(tmp_path))
    assert run.read_layer_metric(metric, result, cell) is None
    assert result["program_spans"] is None and not result["samples"]


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    """`data/BENCHMARK.tiny.json` with the new metrics of the root manifest
    appended, their cells renamed to the tiny ones."""
    with open(os.path.join(HERE, "data", "BENCHMARK.tiny.json")) as f:
        tiny = json.load(f)
    rename = {"c5-serve-steady": "tiny-serve", "c5-train-1chip": "tiny-train-1chip",
              "c5-train-dp4": "tiny-train-dp4"}
    tiny["per_layer"] += [dict(m, workloads=[rename[w] for w in m["workloads"]]) for m in NEW]
    tiny["configs"][0]["file"] = os.path.join(HERE, "data", tiny["configs"][0]["file"])
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.spans.json"
    path.write_text(json.dumps(tiny))
    return str(path)


@pytest.mark.parametrize("workload", ["tiny-serve", "tiny-train-1chip"])
def test_rehearsal_reads_the_span_metrics(workload, manifest):
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import run; "
            "sys.exit(run.main(sys.argv[3:], rehearsal=True, manifest_path=sys.argv[2]))")
    p = subprocess.run(
        [sys.executable, "-c", code, BENCH, manifest, "--workload", workload,
         "--seed", str(2 ** 31 + 12), "--seconds", "2", "--trace", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900,
        capture_output=True, text=True, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    kind = "train" if "train" in workload else "serve"
    mine = {m["name"] for m in NEW if (m["name"].startswith("train") or m["name"].endswith(".train")) == (kind == "train")}
    got = mine & set(line["metrics"])
    assert got == mine & SPANS_ALONE and got
    for name in got:
        assert line["metrics"][name]["value"] > 0
    samples = json.loads(p.stdout.strip().splitlines()[-2])["samples"]
    assert "idle_by_span" not in samples and "span_clock_check_ms" not in samples
