"""The ``gqa_decoder_serve`` kind end to end at a tiny size on the CPU,
through `run.main` with ``rehearsal=True`` under its own manifest (as
`test_decoder_rehearsal.py` does for the other decoder): the last line of
stdout must be the object the contract fixes, `correct` by the logit
comparison with window pages recycled under the judged sessions, and the new
per-layer readers must read what the counters hold (the trace readers find no
device plane and stay silent)."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MANIFEST = os.path.join(HERE, "data", "BENCHMARK.gqa-tiny.json")
SCRIPT = ("import sys; sys.path.insert(0, {bench!r}); import run; "
          "sys.exit(run.main(sys.argv[1:], rehearsal=True, manifest_path={manifest!r}))")


@pytest.mark.parametrize("trace", [0, 1])
def test_gqa_decoder_cell_prints_the_contracts_last_line(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(bench=BENCH, manifest=MANIFEST),
         "--workload", "tiny-gqa-decoder-serve", "--seed", str(2 ** 31 + 11),
         "--seconds", "3", "--trace", str(trace)],
        env=env, timeout=900, capture_output=True, text=True,
        cwd=os.path.dirname(BENCH))
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    line, notes = json.loads(lines[-1]), json.loads(lines[-2])
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert all(notes["checks"].values()), notes["checks"]
    judged = notes["samples"]["reference"]
    assert judged["requests"] == 8 and judged["resident"] >= 1
    assert judged["follow_ups"] == 2 and judged["logit_max"] < 1e-4
    assert notes["samples"]["slots"]["window_pages_recycled"] > 0
    with open(MANIFEST) as f:
        manifest = json.load(f)
    if trace:
        # (the mfu and the rooflines need a chip's published peaks)
        assert {"gqa_experts_touched_mean",
                "kv_full_pool_fill_share", "kv_window_pool_fill_share",
                "window_pages_recycled_per_s", "compile_s",
                "serve_compiles_in_window",
                "decoder_rows_per_step"} <= set(line["metrics"])
        assert line["metrics"]["decoder_rows_per_step"]["value"] >= 1
        assert line["metrics"]["window_pages_recycled_per_s"]["value"] > 0
        assert set(line["metrics"]) <= {m["name"] for m in manifest["per_layer"]}
        assert 0 < line["metrics"]["kv_full_pool_fill_share"]["value"] < 100
    else:
        assert set(line["metrics"]) == {m["name"] for m in manifest["end_to_end"]}


def test_the_cell_and_its_files_are_named_in_the_manifest():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        real = json.load(f)
    cell = {w["name"]: w for w in real["workloads"]}["mellum2-serve-longctx"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2-12b-l8", "gqa-decoder-serve-longctx-0.8knee", 1)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "gqa_decoder_serve"
    assert f"{traffic['rate_per_s']} req/s" in cell["why"]
    assert traffic["rate_per_s"] == pytest.approx(0.8 * traffic["knee"]["knee_per_s"])
    config = {c["name"]: c for c in real["configs"]}["mellum2-12b-l8"]
    assert config["reduced"] == ["num_hidden_layers"]
    mine = [m for m in real["per_layer"] if m.get("workloads") == [cell["name"]]]
    assert len(mine) == 10 and all(m["source"] != "program_span" for m in mine)
    assert any("mfu" in m["name"] and m["moves"] == "serve_tokens_per_s" for m in mine)


def _rows(knee: dict) -> list[dict]:
    keys = ("swept_rates_per_s", "sustained", "offered_tokens_per_s", "tokens_per_s")
    return [dict(zip(("rate_per_s", *keys[1:]), row))
            for row in zip(*(knee[k] for k in keys))]


def test_the_recorded_knee_is_the_one_the_sweep_computes():
    sys.path.insert(0, BENCH)
    import gqa_decoder_sweep
    import loadgen

    with open(os.path.join(BENCH, "traffic",
                           "gqa-decoder-serve-longctx-0.8knee.json")) as f:
        traffic = json.load(f)
    knee = traffic["knee"]
    assert len(knee["swept_rates_per_s"]) >= 6
    assert gqa_decoder_sweep.knee_of(_rows(knee)) == (
        knee["knee_per_s"], knee["capacity_tokens_per_s"])
    assert knee["under_capacity_share"] == gqa_decoder_sweep.UNDER_CAPACITY
    # "the most delivered" is a plateau: two swept rates offer more than it
    assert sum(o > knee["capacity_tokens_per_s"]
               for o in knee["offered_tokens_per_s"]) >= 2
    # what a swept rate offered is what the file's lengths give at that rate
    for i, rate in enumerate(knee["swept_rates_per_s"]):
        due = loadgen.make_schedule({**traffic, "rate_per_s": rate}, 1 + i,
                                    knee["window_s"], preroll_s=knee["preroll_s"])
        offered = sum(a.new_tokens for a in due
                      if 0 <= a.due < knee["window_s"]) / knee["window_s"]
        assert offered == pytest.approx(knee["offered_tokens_per_s"][i])


def test_a_rate_at_capacity_is_not_the_knee_though_its_queue_held():
    sys.path.insert(0, BENCH)
    import gqa_decoder_sweep

    rows = [dict(rate_per_s=r, sustained=s, offered_tokens_per_s=239.0 * r,
                 tokens_per_s=t)
            for r, s, t in [(2, True, 470), (3, True, 700), (4, True, 950),
                            (5, False, 900), (6, False, 940)]]
    assert gqa_decoder_sweep.knee_of(rows) == (3, 950)
    assert gqa_decoder_sweep.knee_of(rows[3:]) == (None, 940)
