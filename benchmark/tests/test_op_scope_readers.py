"""The per-layer device time of the train program (`op_scopes.py` and its
readers): a hand-made trace of chip 0 and the map a ``--profile-dir`` run
writes beside it (`lstm_tensorspark_tpu/utils/op_scopes.py`)."""
import json
import os
import types

import pytest

import op_scopes
import run
from trace_reduce import ChipTrace, Event, Trace

STEPS = 2
MAP = {"jit_core": {
    "ops": {"fusion.1": "embed", "lstm_rec_fwd_tiled.3": "lstm",
            "fusion.4": "lstm", "lm_head_fwd.5": "head", "fusion.6": "head",
            "fusion.7": "optimizer", "all-reduce.8": "dp_sync",
            "all-gather-start.9": "dp_sync", "copy-start.11": "optimizer"},
    "mixed": {"fusion.6": ["head", "optimizer"]}}}


def op(label, start, end, opcode="fusion"):
    return Event(f"%{label} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)", start,
                 end)


def chip():
    """Two runs of ``jit_core`` in the window [0, 20] (one starts before
    it), a run of another program, and operations outside every run."""
    ops = [op("fusion.1", -1.0, 1.0),                       # clipped at 0
           op("lstm_rec_fwd_tiled.3", 1.0, 3.0, "custom-call"),
           op("lstm_rec_bwd.12", 3.0, 4.0, "custom-call"),  # not in the map
           op("fusion.4", 4.0, 5.0),
           op("lm_head_fwd.5", 5.0, 6.5, "custom-call"),
           op("fusion.6", 6.5, 7.0),
           op("fusion.7", 7.0, 8.0),
           op("all-reduce.8", 8.0, 8.5, "all-reduce"),
           op("fusion.10", 8.5, 9.0),                       # under no scope
           op("fusion.1", 9.5, 10.0),                       # another program
           op("fusion.4", 11.0, 12.0),                      # between runs
           op("fusion.7", 13.0, 14.0),
           op("fusion.4", 19.0, 21.0)]                      # clipped at 20
    spans = [op("all-gather-start.9", 7.5, 8.75, "all-gather-start"),
             op("copy-start.11", 7.0, 7.5, "copy-start")]
    modules = [Event("jit_core(7)", -1.0, 9.0),
               Event("jit_eval(8)", 9.5, 10.0),
               Event("jit_core(7)", 13.0, 21.0)]
    return ChipTrace(0, ops, modules, spans)


@pytest.fixture
def cell(tmp_path):
    os.makedirs(tmp_path / "profile")
    with open(tmp_path / "profile" / op_scopes.FILE, "w") as f:
        json.dump(MAP, f)
    model = {"hidden_size": 128, "num_layers": 2, "compute_dtype": "bfloat16"}
    return types.SimpleNamespace(chips=1, workdir=str(tmp_path),
                                 config={"model": model})


def result():
    return {"trace": Trace([chip()], []), "trace_window": (0.0, 20.0),
            "steps": STEPS, "samples": {}, "batch": 8, "seq_len": 16}


def ms(seconds):
    return 1e3 * seconds / STEPS


@pytest.mark.parametrize("metric, seconds", [
    ("recurrence_kernel_ms_per_step", 2.0 + 1.0),      # both lstm_rec_ kernels
    ("train_embed_ms_per_step", 1.0),                  # clipped at the window
    ("train_lstm_stack_ms_per_step", 3.0 + 1.0 + 1.0),  # kernels, fusion.4 x2
    ("train_head_ms_per_step", 1.5 + 0.5),
    ("train_optimizer_ms_per_step", 1.0 + 1.0),
    # leaves [8, 8.5] merged with the asynchronous gather [7.5, 8.75]
    ("dp_sync_ms_per_step", 1.25),
])
def test_each_reader_gives_its_scopes_time_per_step(cell, metric, seconds):
    assert run.read_layer_metric(metric, result(), cell) == pytest.approx(
        ms(seconds))


def test_an_operation_outside_the_mapped_runs_or_the_window_is_not_counted(
        cell):
    r = result()
    split = op_scopes.breakdown(r, cell)
    # busy: [0, 9] and [13, 14], [19, 20] in the runs: not the other
    # program's fusion.1 at 9.5, not fusion.4 at 11 between runs, nor
    # what lies past 20
    assert split["busy_ms_per_step"] == pytest.approx(ms(9.0 + 1.0 + 1.0))
    assert split["ms_per_step"] == pytest.approx({
        "embed": ms(1.0), "lstm": ms(5.0), "head": ms(2.0),
        "optimizer": ms(2.0), "dp_sync": ms(0.5), "unscoped": ms(0.5)})
    assert sum(split["ms_per_step"].values()) == pytest.approx(
        split["busy_ms_per_step"])
    assert split["mixed_fusions_ms_per_step"] == {
        "fusion.6": [pytest.approx(ms(0.5)), ["head", "optimizer"]]}
    assert split["largest_unscoped_ms_per_step"] == {
        "fusion.10": pytest.approx(ms(0.5))}


def test_unscoped_share_is_of_the_busy_time(cell):
    r = result()
    share = run.read_layer_metric("unscoped_busy_share.train", r, cell)
    assert share == pytest.approx(100 * 0.5 / 11.0)
    assert "ms_per_step_by_scope" in r["samples"]


def test_recurrence_roofline_is_over_the_named_kernels_alone(cell,
                                                              monkeypatch):
    import flops

    monkeypatch.setattr(flops, "peaks", lambda kind: {
        "bf16_tflops": 1e-6, "hbm_gbytes_per_s": 1e3})
    r = result()
    share = run.read_layer_metric("recurrence_kernel_roofline", r, cell)
    t_flops = flops.recurrence_train_flops_per_step(8, 16, 128, 2) / 1e6
    assert share == pytest.approx(100 * t_flops / 1.5)   # 3 s over 2 steps
    assert "bound by compute" in r["samples"]["recurrence_kernel_roofline"]


READERS = ["recurrence_kernel_ms_per_step", "recurrence_kernel_roofline",
           "train_lstm_stack_ms_per_step", "train_head_ms_per_step",
           "train_optimizer_ms_per_step", "train_embed_ms_per_step",
           "dp_sync_ms_per_step", "unscoped_busy_share.train"]


@pytest.mark.parametrize("metric", READERS)
def test_every_reader_is_none_without_a_map_or_a_trace(metric, tmp_path):
    bare = types.SimpleNamespace(chips=1, workdir=str(tmp_path), config={})
    assert run.read_layer_metric(metric, result(), bare) is None
    untraced = dict(result(), trace=None, trace_window=None)
    assert run.read_layer_metric(metric, untraced, bare) is None


def test_readers_are_entries_of_the_manifest():
    with open(os.path.join(os.path.dirname(os.path.dirname(
            op_scopes.__file__)), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for metric in READERS:
        assert entries[metric]["source"] == "device_trace"
        assert entries[metric]["moves"] == "train_tokens_per_s"
    assert entries["dp_sync_ms_per_step"]["workloads"] == ["c5-train-dp4"]
