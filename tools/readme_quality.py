#!/usr/bin/env python3
"""Regenerate README.md's wall-clock-to-quality table from
BASELINE_MEASURED.json, so the prose cannot go stale against the record.
Mechanical:

    python3 tools/readme_quality.py          # rewrite README.md in place
    python3 tools/readme_quality.py --check  # exit 1 if README is stale

The generator owns ONLY the table block between the quality-table header
and the first non-table line (surrounding prose stays hand-written). A
config whose entry carries the r5 ``invalidated`` marker (task changed,
TPU leg not yet re-measured) renders an honest pending row built from
its banked CPU curve instead of a cross-task speedup.
"""

import argparse
import json
import os
import re
import sys

_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = os.path.join(_DIR, "README.md")
CACHE = os.path.join(_DIR, "BASELINE_MEASURED.json")

_LABELS = {
    "config1_ptb_char": "1 — PTB char 1×128",
    "config2_imdb": "2 — IMDB bi-LSTM 256",
    "config3_wikitext2": "3 — WikiText-2 2×650",
    "config4_uci": "4 — UCI seq2seq 2×256",
    "config5_wikitext103": "5 — WT-103 4×1024",
}

_METRICS = {
    "eval_ppl": ("ppl", "≤"),
    "eval_accuracy": ("accuracy", "≥"),
    "eval_mse": ("free-run MSE", "≤"),
}


def _fmt_target(metric: str, target: float) -> str:
    name, cmp = _METRICS.get(metric, (metric, "@"))
    t = f"{target:g}"
    return f"{name} {cmp} {t}"


def _cpu_reached(entry: dict):
    """(target, seconds) at the tightest target the banked CPU leg
    reached, for pending rows. Target keys preserve insertion order =
    loosest → tightest (bench_quality CONFIGS orders them that way)."""
    targets = (entry.get("cpu") or {}).get("targets") or {}
    if not targets:
        return None
    tight = list(targets)[-1]
    return tight, targets[tight]["t"]


def _vintage(entry: dict) -> str:
    """Both legs' measurement dates when they differ — a row combining a
    fresh TPU leg with an older banked CPU leg must say so."""
    tv = entry.get("tpu_measured_at")
    cv = entry.get("cpu_measured_at")
    if tv and cv and tv != cv:
        return f" (tpu {tv}, cpu {cv})"
    if tv or cv:
        return f" ({tv or cv})"
    return ""


def render(results: dict) -> str:
    rows = [
        "| Config | Metric @ target | TPU | CPU "
        "| Speedup (incl. compile / post-compile / warm) |",
        "|---|---|---|---|---|",
    ]
    for name, label in _LABELS.items():
        entry = results.get(name) or {}
        metric = entry.get("metric", "?")
        summary = entry.get("summary")
        invalidated = "invalidated" in entry
        # the marker is authoritative: a stale cross-task summary must
        # never render as a measured row just because the key survived
        if invalidated or not isinstance(summary, dict):
            reached = _cpu_reached(entry)
            cpu_s = "—"
            if reached:
                tight, secs = reached
                cpu_s = f"{secs:.1f} s to {_fmt_target(metric, float(tight))}"
                when = entry.get("cpu_measured_at")
                if when:
                    cpu_s += f" (banked {when})"
            state = ("*no TPU leg on the new task*" if invalidated
                     else "*no common target reached*")
            task = "(new task)" if invalidated else "—"
            rows.append(f"| {label} | {task} | {state} | {cpu_s} | — |")
            continue
        # measured row: cold and warm halves are EACH optional (a
        # warm-only summary is legal — bench_quality's _summarize builds
        # it when only the warm legs share a common target)
        target = summary.get("target", summary.get("warm_target"))
        target_s = (_fmt_target(metric, target) if target is not None
                    else "—")
        cold = "target" in summary
        tpu_s = f"{summary['tpu_seconds']:.1f} s" if cold else "—"
        cpu_s = f"{summary['cpu_seconds']:.1f} s" if cold else "—"
        if cold:
            speed = (f"{summary['speedup']:.1f}× / "
                     f"**{summary['speedup_train']:.1f}×**")
        else:
            speed = "— / —"
        warm = summary.get("speedup_warm")
        speed += (f" / {warm:.1f}×" if isinstance(warm, (int, float))
                  else " / —")
        speed += _vintage(entry)
        rows.append(f"| {label} | {target_s} | {tpu_s} | {cpu_s} "
                    f"| {speed} |")
    return "\n".join(rows)


_BLOCK = re.compile(
    r"(\| Config \| Metric @ target \| TPU \| CPU \|[^\n]*\|\n)(?:\|.*\n)+"
)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if README's quality table is stale")
    args = ap.parse_args()

    with open(CACHE) as f:
        results = json.load(f)["quality"]["results"]
    with open(README) as f:
        readme = f.read()
    m = _BLOCK.search(readme)
    if not m:
        print("README quality-table block not found (markers changed?)",
              file=sys.stderr)
        return 2
    new_block = render(results) + "\n"
    if readme[m.start():m.end()] == new_block:
        print("README quality table is in sync with BASELINE_MEASURED.json")
        return 0
    if args.check:
        print("README quality table is STALE vs BASELINE_MEASURED.json "
              "(run tools/readme_quality.py)", file=sys.stderr)
        return 1
    with open(README, "w") as f:
        f.write(readme[:m.start()] + new_block + readme[m.end():])
    print("README quality table regenerated from BASELINE_MEASURED.json")
    return 0


if __name__ == "__main__":
    sys.exit(main())
