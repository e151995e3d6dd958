"""tools/readme_quality.py: the generated wall-clock-to-quality table —
measured rows render coherent summaries with vintage, invalidated rows
render honest pending cells from the banked CPU curve, and the committed
README is in sync with BASELINE_MEASURED.json."""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import readme_quality  # noqa: E402


def test_render_measured_and_pending_rows():
    results = {
        "config1_ptb_char": {
            "metric": "eval_ppl",
            "summary": {"target": 2.0, "tpu_seconds": 33.6,
                        "cpu_seconds": 53.5, "speedup": 1.59,
                        "speedup_train": 12.45, "speedup_warm": 8.25},
            "tpu_measured_at": "2026-08-01",
            "cpu_measured_at": "2026-07-31",
        },
        "config2_imdb": {
            "metric": "eval_accuracy",
            "invalidated": "task changed",
            "cpu": {"targets": {"0.55": {"t": 219.0}, "0.8": {"t": 1062.6}}},
            "cpu_measured_at": "2026-07-31",
        },
        # warm-only summary (only the warm legs share a common target):
        # legal output of bench_quality._summarize — must render, not crash
        "config3_wikitext2": {
            "metric": "eval_ppl",
            "summary": {"warm_target": 60.0, "speedup_warm": 78.28,
                        "tpu_seconds_warm": 8.3, "cpu_seconds_warm": 647.0},
        },
        # stale summary + invalidated marker: the marker wins — the
        # cross-task speedup must NOT render as a measured row
        "config4_uci": {
            "metric": "eval_mse",
            "invalidated": "task changed",
            "summary": {"target": 0.05, "tpu_seconds": 31.7,
                        "cpu_seconds": 148.9, "speedup": 4.7,
                        "speedup_train": 76.11},
        },
    }
    out = readme_quality.render(results)
    lines = out.splitlines()
    assert lines[0].startswith("| Config | Metric @ target | TPU | CPU |")
    row1 = next(l for l in lines if "PTB char" in l)
    assert "ppl ≤ 2" in row1 and "33.6 s" in row1 and "53.5 s" in row1
    assert "**12.4×**" in row1 and "8.2×" in row1
    # split vintages: both legs' dates appear when they differ
    assert "tpu 2026-08-01" in row1 and "cpu 2026-07-31" in row1
    row2 = next(l for l in lines if "IMDB" in l)
    assert "no TPU leg on the new task" in row2
    # pending CPU cell uses the TIGHTEST reached target of the banked leg
    assert "1062.6 s to accuracy ≥ 0.8" in row2
    assert "banked 2026-07-31" in row2
    row3 = next(l for l in lines if "WikiText-2" in l)
    assert "ppl ≤ 60" in row3 and "— / — / 78.3×" in row3
    row4 = next(l for l in lines if "UCI" in l)
    assert "no TPU leg on the new task" in row4 and "4.7×" not in row4
    # configs with no entry at all render a no-common-target row
    row5 = next(l for l in lines if "WT-103" in l)
    assert "no common target" in row5


def test_committed_readme_in_sync():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "tools/readme_quality.py", "--check"],
        capture_output=True, text=True, cwd=repo, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    # and the generator's source of truth parses
    json.load(open(os.path.join(repo, "BASELINE_MEASURED.json")))
