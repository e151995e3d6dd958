"""The reduction from a trace to numbers: interval arithmetic on hand-made
events, and the reading of a small trace recorded on the chip
(`data/tiny_tpu.xplane.pb`: four runs of a three-matmul program on one TPU
v5 lite, with the benchmark's marks around and between them)."""
import os

import pytest

import trace_reduce as tr
from trace_reduce import ChipTrace, Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "tiny_tpu.xplane.pb")


def chip():
    ops = [Event("%fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(f32[8]{0} %p), kind=kLoop", 0.0, 1.0),
           Event("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %g), to_apply=%add", 1.0, 2.0),
           Event("%jvp__.3 = (f32[8]{0:T(128)}, f32[8]{0}) custom-call(f32[8]{0} %x), custom_call_target=\"tpu_custom_call\"", 3.0, 4.0),
           Event("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop", 5.5, 7.0)]
    spans = [Event("%all-reduce-start.8 = f32[8]{0} all-reduce-start(f32[8]{0} %h)", 5.0, 6.0)]
    modules = [Event("jit_step(123)", 0.0, 4.0), Event("jit_step(123)", 5.0, 7.0)]
    return ChipTrace(0, ops, modules, spans)


def test_label_opcode_and_report_name_come_out_of_the_hlo_text():
    e = chip().ops[2]
    assert (e.label, e.opcode) == ("jvp__.3", "custom-call")
    assert chip().ops[0].short == "fusion.1 = f32[8,128] fusion(f32[8] %p), kind=kLoop"
    w = Event("%while.5 = (s32[]{:T(128)}, f32[4]{0:T(1024)S(1)}) while((s32[]{:T(128)}) %t), body=%b", 0, 1)
    assert w.opcode == "while" and w.opcode in tr.CONTAINERS


def test_union_clip_subtract():
    assert tr.union([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == [(0, 2), (3, 4)]
    assert tr.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]


def test_busy_is_the_union_not_the_sum():
    assert tr.busy_seconds(chip(), 0.0, 7.0) == pytest.approx(4.5)   # 2 + 1 + 1.5
    assert tr.busy_seconds(chip(), 1.0, 3.5) == pytest.approx(1.5)


def test_seconds_per_operation_and_exposed_part():
    c = chip()
    spans = tr.collective_spans(c, "all-reduce")
    assert spans == [(1.0, 2.0), (5.0, 6.0)]          # one synchronous, one async
    # the first runs alone; [5.5, 6.0] of the second is hidden under fusion.2
    assert tr.exposed_seconds(c, 0, 7, "all-reduce") == pytest.approx(1.5)
    assert tr.opcode_seconds(c, 0, 7, "custom-call") == pytest.approx(1.0)
    assert tr.opcode_seconds(c, 3.5, 7, "custom-call") == pytest.approx(0.5)


def test_gaps_are_given_to_a_mark_or_to_the_program_before_them():
    trace = Trace([chip()], [Event("bench:log_record", 4.5, 4.5)])
    gaps = tr.idle_gaps(chip(), 0.0, 7.0)
    assert [(round(s, 3), round(e, 3)) for s, e, _ in gaps] == [(2.0, 3.0), (4.0, 5.5)]
    b = tr.breakdown(trace, 0.0, 7.0, sync_mark="bench:log_record")
    assert dict(b["idle_gaps"]) == {"bench:log_record": pytest.approx(1.5),
                                    "host:unattributed after jit_step": pytest.approx(1.0)}
    assert b["device_ops"][0][0].startswith("fusion.2 = f32[8] fusion(")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_reads_a_trace_recorded_on_the_chip():
    trace = tr.load(RECORDED)
    assert [c.chip for c in trace.chips] == [0]
    c = trace.chips[0]
    runs = [m for m in c.modules if "tiny_step" in m.name]
    assert len(runs) == 4
    names = [m.name for m in trace.marks]
    assert names[0] == "bench:window_open" and names[-1] == "bench:window_close"
    assert names.count("bench:log_record") == 4
    lo, hi = trace.marks[0].start, trace.marks[-1].start
    busy = tr.busy_seconds(c, lo, hi)
    assert 0 < busy < hi - lo
    # every operation lies inside a program run, on the same clock
    assert all(any(m.start <= e.start and e.end <= m.end + 1e-6 for m in c.modules)
               for e in c.ops)
    # the host slept 2 ms after each run: the gaps are there and are the sync's
    b = tr.breakdown(trace, lo, hi, sync_mark="bench:log_record")
    assert dict(b["idle_gaps"])["bench:log_record"] > 0.004
