#!/usr/bin/env python
"""Wall-clock-to-quality: the QUALITY half of the north star, ALL 5 configs.

``BASELINE.json:metric`` is "seq/sec/chip; wall-clock to reference
perplexity" — this harness measures the second half for every BASELINE.md
config: train the IDENTICAL config (same synthetic corpus, same seed, same
hyperparameters) on the TPU and on single-process CPU (the offline stand-in
for the reference's Spark-CPU executors), log the task's eval-quality curve
to JSONL, and record the first wall-clock time each run reaches each target.

Per-config quality metric (VERDICT r2 item 2):
- configs 1/3/5 (LM): eval perplexity, lower is better;
- config 2 (IMDB bi-LSTM): eval accuracy, higher is better;
- config 4 (UCI seq2seq): free-running eval MSE, lower is better.

Outputs:
- ``quality_curves/<config>_<platform>.jsonl`` — full metric curves (the
  CLI's own JSONL: {"t": seconds, "step", <metric>, ...});
- ``BASELINE_MEASURED.json`` gains a "quality" section:
  time-to-target per config/platform + the TPU speedup at the tightest
  target both platforms reached.

Timing honesty: "t" counts from process logger start (includes compile —
the launch-to-quality number); "t_train" additionally subtracts the time of
the first logged training record (post-compile steady-state). Both are
reported. Each eval fetches loss values to the host, so the clock stops on
finished work.

Each platform runs its FASTEST HONEST configuration of the same model/data/
optimizer (identical math; trajectories agree to float tolerance): the TPU
legs add --use-pallas (fused recurrence kernels), K-step dispatch batching
where the per-dispatch cost would otherwise dominate
(tests/test_multistep.py proves K-step parity), and --device-data
--fused-eval (the eval pass runs inside the train executable on
device-resident eval data — identical eval math, tests/test_fused_eval.py,
but zero train/eval executable swaps); the CPU legs stay per-step —
compute-bound, and faithful to the reference's one-Spark-round-per-step.
NOTE: with --steps-per-call K, --log-every/--eval-every count CALLS
(train_loop contract), so TPU cadences are pre-divided by K below;
--num-steps still counts optimizer steps.

Each config/platform additionally measures a WARM-CACHE leg: a few-step
run populates a fresh compilation-cache directory, handed to the child as
JAX_COMPILATION_CACHE_DIR (same program shapes → the same executables
compile and cache; the cold leg gets an EMPTY directory the same way, so
the checkout's own .jax_cache never warms it), then a full run against it gives
the launch-to-quality number a REPEAT run sees — XLA compilation is a
once-per-program-shape cost, so cold (first-ever run) and warm (every run
after) are both honest, and both are reported (``summary.speedup`` cold,
``summary.speedup_warm`` warm).

Run: ``python bench_quality.py [config ...]`` (TPU visible; the CPU leg
runs in a subprocess with JAX_PLATFORMS=cpu).
"""

from __future__ import annotations

import datetime
import json
import os
import shutil
import subprocess
import sys

_DIR = os.path.dirname(os.path.abspath(__file__))
CURVES = os.path.join(_DIR, "quality_curves")
CACHE = os.path.join(_DIR, "BASELINE_MEASURED.json")
LEG_TIMEOUT_S = 2200  # > 2x the slowest expected leg (config2 CPU ~1000 s
                      # at the r4 discriminating-task step budget)

# Targets are ordered loose → tight; the summary reports the tightest one
# BOTH platforms reached inside the step budget. r4 (VERDICT r3 weak 2):
# the synthetic tasks were hardened (controlled-entropy word corpora,
# low-SNR classifier — data/corpus.py synthetic_word_corpus,
# datasets.py imdb(signal=...)) so curves decline across hundreds of
# steps, and the target lists are DENSE so the tightest common target
# lands mid-curve wherever the plateau turns out to be.
PPL_TARGETS = [12.0, 10.0, 8.0, 6.0, 5.0, 4.5, 4.0, 3.5, 3.0, 2.5, 2.0]

CONFIGS = {
    "config1_ptb_char": dict(
        metric="eval_ppl", mode="min", targets=PPL_TARGETS,
        argv=[
            "--dataset", "ptb_char", "--hidden-units", "128",
            "--num-layers", "1", "--batch-size", "64", "--seq-len", "64",
            "--learning-rate", "1.0", "--num-steps", "800",
            "--log-every", "50", "--eval-every", "100", "--backend", "single",
        ],
        # --fused-eval: the eval pass runs INSIDE the train executable on a
        # device-resident valid stream (no train/eval program swap).
        # Eval cadence 4 calls = 100 steps, matching the CPU
        # leg's --eval-every 100 exactly: both platforms can detect a
        # target crossing at the same optimizer steps (unequal cadences
        # would bias time-to-target toward the finer-grained leg)
        tpu_extra=["--use-pallas", "--steps-per-call", "25",
                   "--device-data", "--fused-eval",
                   "--log-every", "2", "--eval-every", "4"],
    ),
    # signal=0.25 synthetic task (datasets.py): accuracy climbs over
    # ~200+ steps instead of saturating at step 40 — the race spends its
    # wall-clock training on both platforms
    "config2_imdb": dict(
        metric="eval_accuracy", mode="max",
        targets=[0.55, 0.60, 0.65, 0.70, 0.75, 0.80, 0.85, 0.90, 0.95],
        argv=[
            "--dataset", "imdb", "--hidden-units", "256", "--num-layers", "1",
            "--batch-size", "64", "--seq-len", "400",
            "--learning-rate", "0.2", "--num-steps", "240",
            "--log-every", "20", "--eval-every", "20", "--backend", "single",
        ],
        tpu_extra=["--use-pallas", "--steps-per-call", "10",
                   "--device-data", "--fused-eval",
                   "--log-every", "2", "--eval-every", "2"],
    ),
    # controlled-entropy 1,000-word stand-in: ppl descends through the
    # unigram level (~hundreds) into the bigram structure over 400 steps
    "config3_wikitext2": dict(
        metric="eval_ppl", mode="min",
        targets=[300.0, 200.0, 150.0, 100.0, 80.0, 60.0, 50.0, 40.0, 30.0,
                 25.0, 20.0, 15.0, 12.0, 10.0, 8.0, 6.0, 5.0, 4.0, 3.0],
        argv=[
            "--dataset", "wikitext2", "--hidden-units", "650",
            "--num-layers", "2", "--batch-size", "64", "--seq-len", "35",
            "--learning-rate", "1.0", "--num-steps", "400",
            "--log-every", "25", "--eval-every", "50", "--backend", "single",
        ],
        # eval cadence 2 calls = 50 steps = the CPU leg's --eval-every 50
        tpu_extra=["--use-pallas", "--steps-per-call", "25",
                   "--device-data", "--fused-eval",
                   "--log-every", "1", "--eval-every", "2"],
    ),
    "config4_uci": dict(
        metric="eval_mse", mode="min",
        targets=[0.5, 0.3, 0.2, 0.15, 0.12, 0.10, 0.08, 0.05],
        argv=[
            "--dataset", "uci_electricity", "--hidden-units", "256",
            "--num-layers", "2", "--batch-size", "64", "--seq-len", "168",
            "--learning-rate", "0.05", "--num-steps", "150",
            "--log-every", "15", "--eval-every", "15", "--backend", "single",
        ],
        tpu_extra=["--use-pallas", "--steps-per-call", "15",
                   "--device-data", "--fused-eval",
                   "--log-every", "1", "--eval-every", "1"],
    ),
    # bounded-step time-to-ppl at WT-103-class scale: 100 steps is the
    # bound (CPU ~7-9 s/step at these dims with the 5,000-word stand-in);
    # dense targets from the ~5,000 init ppl down through the unigram
    # level so the tightest common target lands mid-curve;
    # lr 0.5 — 1.0 diverges at H=1024/L=4 bf16
    "config5_wikitext103": dict(
        metric="eval_ppl", mode="min",
        targets=[3000.0, 2000.0, 1500.0, 1000.0, 700.0, 500.0, 400.0,
                 300.0, 250.0, 200.0, 150.0, 120.0, 100.0, 80.0, 60.0,
                 50.0, 40.0, 30.0, 25.0, 20.0, 15.0, 12.0, 10.0],
        argv=[
            "--dataset", "wikitext103", "--hidden-units", "1024",
            "--num-layers", "4", "--batch-size", "32", "--seq-len", "64",
            "--learning-rate", "0.5", "--num-steps", "100",
            "--log-every", "10", "--eval-every", "20",
            "--eval-batches", "4", "--backend", "single",
        ],
        tpu_extra=["--use-pallas", "--steps-per-call", "5",
                   "--device-data", "--fused-eval",
                   "--log-every", "2", "--eval-every", "4"],
    ),
}


def run_leg(name: str, platform: str, *, cache_dir: str,
            num_steps: int | None = None, tag: str = "") -> str:
    """Run one training leg, return the JSONL path.

    ``cache_dir`` is the child's JAX_COMPILATION_CACHE_DIR (the CLI sets
    no directory of its own when that is set); ``tag`` suffixes the output
    curve filename (warm/populate legs must NOT clobber the cold curve).
    ``num_steps`` overrides the step budget (used for the cheap
    cache-populate run: same program SHAPES, so the same executables
    compile and cache, but only a few optimizer steps execute)."""
    os.makedirs(CURVES, exist_ok=True)
    jsonl = os.path.join(CURVES, f"{name}_{platform}{tag}.jsonl")
    if os.path.exists(jsonl):
        os.remove(jsonl)
    spec = CONFIGS[name]
    argv = list(spec["argv"])
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": cache_dir}
    if platform == "tpu":
        argv += spec.get("tpu_extra", [])
    else:
        env["JAX_PLATFORMS"] = "cpu"
    if num_steps is not None:
        argv += ["--num-steps", str(num_steps)]
    argv += ["--jsonl", jsonl]
    try:
        proc = subprocess.run([sys.executable, "main.py", *argv], cwd=_DIR,
                              env=env, capture_output=True, text=True,
                              timeout=LEG_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        raise RuntimeError(
            f"{name}/{platform}{tag} ran past {LEG_TIMEOUT_S}s — killed; "
            "curve so far is on disk"
        )
    if proc.returncode != 0:
        raise RuntimeError(
            f"{name}/{platform}{tag} failed rc={proc.returncode}: "
            f"{proc.stderr[-2000:]}"
        )
    return jsonl


def _fresh_cache(name: str, platform: str, tag: str = "") -> str:
    """An EMPTY compilation-cache directory for one leg (gitignored)."""
    cache = os.path.join(CURVES, f".xla_{name}_{platform}{tag}")
    shutil.rmtree(cache, ignore_errors=True)
    return cache


def time_to_targets(jsonl: str, metric: str, mode: str, targets) -> dict:
    """Scan the curve: first wall-clock at/beyond each quality target."""
    evals = []
    first_step_t = None
    for line in open(jsonl):
        r = json.loads(line)
        if first_step_t is None and "loss" in r and "step" in r:
            first_step_t = r["t"]
        if metric in r:
            evals.append((r["t"], r[metric], r.get("step")))
    out = {"metric": metric, "targets": {},
           "final": evals[-1][1] if evals else None,
           "first_step_t": first_step_t}
    reached = (
        (lambda v, tgt: v <= tgt) if mode == "min"
        else (lambda v, tgt: v >= tgt)
    )
    for tgt in targets:
        hit = next((e for e in evals if reached(e[1], tgt)), None)
        if hit:
            out["targets"][str(tgt)] = {
                "t": hit[0],
                "t_train": round(hit[0] - (first_step_t or 0.0), 3),
                "step": hit[2],
            }
    return out


def _tightest_common(spec, a: dict, b: dict):
    """Tightest target reached by BOTH platforms' target maps, or None."""
    both = [t for t in map(str, spec["targets"])
            if t in a["targets"] and t in b["targets"]]
    return both[-1] if both else None


def _write_cache(results) -> None:
    """Write results incrementally (after EVERY config) so a hung or killed
    leg loses at most the config in flight."""
    cache = {}
    if os.path.exists(CACHE):
        with open(CACHE) as f:
            cache = json.load(f)
    cache["quality"] = {
        "note": ("wall-clock to quality target (ppl / accuracy / mse per "
                 "task), identical config+data+seed on TPU vs single-process "
                 "CPU (Spark-CPU stand-in); t includes compile, t_train is "
                 "post-compile; *_warm legs repeat the run against a "
                 "populated compilation cache (launch-to-quality without "
                 "the once-per-shape XLA compile)"),
        "results": results,
    }
    with open(CACHE, "w") as f:
        json.dump(cache, f, indent=1)


def _summarize(name, spec, results) -> None:
    """Recompute the config's cold + warm summaries from its target maps."""
    entry = results[name]
    if "tpu" in entry and "cpu" in entry:
        tight = _tightest_common(spec, entry["tpu"], entry["cpu"])
        if tight:
            tt = entry["tpu"]["targets"][tight]
            tc = entry["cpu"]["targets"][tight]
            entry["summary"] = {
                "metric": spec["metric"],
                "target": float(tight),
                "tpu_seconds": tt["t"],
                "cpu_seconds": tc["t"],
                "speedup": round(tc["t"] / tt["t"], 2),
                "tpu_seconds_train": tt["t_train"],
                "cpu_seconds_train": tc["t_train"],
                "speedup_train": round(
                    tc["t_train"] / max(tt["t_train"], 1e-9), 2),
            }
            print(f"[bench_quality] {name}: {spec['metric']} @ {tight} "
                  f"TPU {tt['t']:.1f}s vs CPU {tc['t']:.1f}s "
                  f"({entry['summary']['speedup']}x; "
                  f"post-compile {entry['summary']['speedup_train']}x)",
                  flush=True)
    if "tpu_warm" in entry and "cpu_warm" in entry:
        tight_w = _tightest_common(spec, entry["tpu_warm"], entry["cpu_warm"])
        if tight_w:
            tt = entry["tpu_warm"]["targets"][tight_w]
            tc = entry["cpu_warm"]["targets"][tight_w]
            entry.setdefault("summary", {}).update({
                "warm_target": float(tight_w),
                "tpu_seconds_warm": tt["t"],
                "cpu_seconds_warm": tc["t"],
                "speedup_warm": round(tc["t"] / tt["t"], 2),
            })
            print(f"[bench_quality] {name} warm launch-to-target @ "
                  f"{tight_w}: TPU {tt['t']:.1f}s vs CPU {tc['t']:.1f}s "
                  f"({entry['summary']['speedup_warm']}x)", flush=True)


def main(only: list[str] | None = None, *, mode: str = "full",
         platforms=("tpu", "cpu")) -> int:
    """mode: "full" = run cold + warm legs; "warm" = run only the
    populate+warm legs (cold results recomputed from existing curves);
    "recompute" = no runs, rebuild every result from the curves on disk.
    ``platforms`` restricts which legs RUN (results for the other platform
    are still recomputed from curves on disk when present) — lets the CPU
    halves bank while the TPU is unavailable, and vice versa."""
    # merge into any existing results so single-config reruns keep the rest
    results = {}
    if os.path.exists(CACHE):
        with open(CACHE) as f:
            results = json.load(f).get("quality", {}).get("results", {})
    for name in (only or CONFIGS):
        spec = CONFIGS[name]
        # PRESERVE previously persisted results: a warm-only/recompute pass
        # on a machine missing some curve files must not erase the entries
        # it cannot rebuild — only overwrite what this pass measured/reread
        results[name] = {**(results.get(name) or {}),
                         "metric": spec["metric"]}
        for platform in ("tpu", "cpu"):
            run_this = platform in platforms
            cold_jsonl = os.path.join(CURVES, f"{name}_{platform}.jsonl")
            if mode == "full" and run_this:
                print(f"[bench_quality] {name} on {platform} ...", flush=True)
                cold_jsonl = run_leg(
                    name, platform,
                    cache_dir=_fresh_cache(name, platform, "_cold"))
                # per-leg vintage: tools/readme_quality.py renders it so
                # every published number carries when it was measured
                results[name][platform + "_measured_at"] = (
                    datetime.date.today().isoformat())
                if platform == "tpu":
                    # a fresh TPU measurement resolves any r5
                    # task-change invalidation marker (the marker means
                    # "the TPU half predates the current task")
                    results[name].pop("invalidated", None)
            if os.path.exists(cold_jsonl):
                results[name][platform] = time_to_targets(
                    cold_jsonl, spec["metric"], spec["mode"], spec["targets"]
                )
            warm_jsonl = os.path.join(CURVES, f"{name}_{platform}_warm.jsonl")
            if mode in ("full", "warm") and run_this:
                # warm-cache leg: the LAUNCH-to-quality number a repeat run
                # sees. Populate the cache with a few-step run (same
                # program shapes → same executables compile+cache), then
                # measure a full run against it.
                cache = _fresh_cache(name, platform)
                print(f"[bench_quality] {name} on {platform} (warm cache) "
                      "...", flush=True)
                k = next((int(spec["tpu_extra"][i + 1])
                          for i, a in enumerate(spec.get("tpu_extra", []))
                          if a == "--steps-per-call"), 1) \
                    if platform == "tpu" else 1
                run_leg(name, platform, cache_dir=cache, num_steps=2 * k,
                        tag="_populate")
                warm_jsonl = run_leg(name, platform, cache_dir=cache,
                                     tag="_warm")
            if os.path.exists(warm_jsonl):
                results[name][platform + "_warm"] = time_to_targets(
                    warm_jsonl, spec["metric"], spec["mode"], spec["targets"]
                )
        _summarize(name, spec, results)
        _write_cache(results)

    print(json.dumps({"quality": {
        n: r.get("summary", "no common target") for n, r in results.items()
    }}))
    return 0


if __name__ == "__main__":
    argv = sys.argv[1:]
    mode = "full"
    for flag, m in (("--recompute", "recompute"), ("--warm-only", "warm")):
        if flag in argv:
            mode = m
            argv.remove(flag)
    platforms = ("tpu", "cpu")
    if "--platform" in argv:
        i = argv.index("--platform")
        if i + 1 >= len(argv) or argv[i + 1] not in ("tpu", "cpu"):
            raise SystemExit("--platform takes exactly one of: tpu, cpu")
        platforms = (argv[i + 1],)
        del argv[i:i + 2]
    sys.exit(main(argv or None, mode=mode, platforms=platforms))
