"""The ``decoder_serve`` kind end to end at a tiny size on the CPU, through
`run.main` with ``rehearsal=True`` as `tests/rehearse.py` calls it (that
file names `BENCHMARK.tiny.json`; this one names the decoder's manifest):
the last line of stdout must be the object the contract fixes, `correct` by
the logit comparison, and the new per-layer readers must read what the
counters hold (the trace readers find no device plane and stay silent)."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MANIFEST = os.path.join(HERE, "data", "BENCHMARK.decoder-tiny.json")
SCRIPT = ("import sys; sys.path.insert(0, {bench!r}); import run; "
          "sys.exit(run.main(sys.argv[1:], rehearsal=True, manifest_path={manifest!r}))")


@pytest.mark.parametrize("trace", [0, 1])
def test_decoder_cell_prints_the_contracts_last_line(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(bench=BENCH, manifest=MANIFEST),
         "--workload", "tiny-decoder-serve", "--seed", str(2 ** 31 + 11),
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
    with open(MANIFEST) as f:
        manifest = json.load(f)
    if trace:
        assert {"moe_pairs_here_share", "experts_touched_mean",
                "latent_cache_fill_share", "compile_s",
                "serve_compiles_in_window",
                "decoder_rows_per_step"} <= set(line["metrics"])
        assert line["metrics"]["decoder_rows_per_step"]["value"] >= 1
        assert set(line["metrics"]) <= {m["name"] for m in manifest["per_layer"]}
        assert 0 < line["metrics"]["moe_pairs_here_share"]["value"] < 100
    else:
        assert set(line["metrics"]) == {m["name"] for m in manifest["end_to_end"]}


def test_every_new_reader_and_file_is_named_in_the_manifest():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        real = json.load(f)
    names = {m["name"] for m in real["per_layer"]}
    for path in os.listdir(os.path.join(BENCH, "layer_metrics")):
        if path.endswith(".py"):
            assert path[:-3] in names, path
    cells = {w["name"]: w for w in real["workloads"]}
    assert cells["dsv2-serve-resident"]["traffic"] == "decoder-serve-resident-0.8knee"
    for w in real["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
