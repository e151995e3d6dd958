#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the machine it is started on.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data that this file finds by the names
in `BENCHMARK.json` (README.md beside this file): the configuration
(`configs/<config>.json`), the traffic (`traffic/<traffic>.json`, whose
``kind`` names the module `<kind>_cell.py` that runs it) and one reader per
per-layer metric (`layer_metrics/<metric>.py`). The last line of stdout is
the one JSON object the contract fixes; with ``--trace 0`` its metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of a short window of the same run.

It refuses anything but a TPU with the chips the cell asks for, and prints no
result then. (The tests rehearse at tiny sizes through `tests/rehearse.py`,
which calls `main` with its own manifest and ``rehearsal=True``; there is no
flag for that here.)
"""

from __future__ import annotations

import time

T0 = time.perf_counter()          # process start, as near as Python gives it

import argparse                   # noqa: E402
import dataclasses                # noqa: E402
import importlib                  # noqa: E402
import importlib.util             # noqa: E402
import json                       # noqa: E402
import math                       # noqa: E402
import os                         # noqa: E402
import shutil                     # noqa: E402
import sys                        # noqa: E402
import tempfile                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    """What one run is given."""
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int              # the program's seed (31 bits of --seed)
    seconds: float
    trace: bool
    t0: float
    workdir: str
    rehearsal: bool
    compiles: object = None


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, manifest_path=None):
    """``(manifest, entry, config, traffic)`` of one cell, found by the names
    in the manifest (`BENCHMARK.json` at the root unless a test names its
    own): `<dir>/configs/<config>.json` runs with
    `<dir>/traffic/<traffic>.json`."""
    manifest = load_json(manifest_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has: {', '.join(cells)})")
    entry = cells[workload]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    base = os.path.dirname(os.path.abspath(manifest_path)) if manifest_path else ROOT
    config_path = os.path.join(base, files[entry["config"]])
    traffic = load_json(os.path.dirname(os.path.dirname(config_path)),
                        "traffic", entry["traffic"] + ".json")
    return manifest, entry, load_json(config_path), traffic


def metrics_of(manifest: dict, group: str, workload: str) -> list[dict]:
    return [m for m in manifest[group]
            if workload in m.get("workloads", [workload])]


def read_layer_metric(name: str, result: dict, cell: Cell):
    """`layer_metrics/<name>.py`'s ``read(result, cell)``; a reader that
    finds nothing to read returns None and the metric is left out."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(result, cell)


def main(argv=None, *, manifest_path=None, rehearsal=False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest, entry, config, traffic = load_cell(args.workload, manifest_path)

    sys.path[:0] = [HERE, ROOT]
    try:
        import lstm_tensorspark_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        raise SystemExit(f"the program is not in this checkout: {e}")
    import jax

    devices = jax.devices()
    if not rehearsal and devices[0].platform != "tpu":
        raise SystemExit(f"JAX found no TPU (platform {devices[0].platform}): "
                         "the benchmark measures on the chip or not at all")
    if len(devices) < entry["chips"]:
        raise SystemExit(f"{args.workload} needs {entry['chips']} chip(s), "
                         f"JAX reports {len(devices)}")

    import observe
    from lstm_tensorspark_tpu.utils.compile_cache import place_compile_cache

    if not rehearsal:
        place_compile_cache()   # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    # scratch files (corpus, JSONL, trace) under TMPDIR, gone at the end
    workdir = tempfile.mkdtemp(prefix="bench-")
    cell = Cell(name=args.workload, config=config, traffic=traffic,
                chips=entry["chips"], seed=args.seed % (2 ** 31 - 1),
                seconds=args.seconds, trace=bool(args.trace), t0=T0,
                workdir=workdir, rehearsal=rehearsal)
    try:
        with observe.Compiles() as compiles:
            cell.compiles = compiles
            runner = importlib.import_module(traffic["kind"] + "_cell")
            result = runner.run(cell)
        result["compile_s"] = compiles.seconds
        result["compile_cache_hits"] = compiles.cache_hits
        line = report(manifest, cell, result)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"samples": result["samples"], "checks": result["checks"],
                      "compile_s": compiles.seconds,
                      "compile_cache_hits": compiles.cache_hits},
                     default=str), flush=True)
    print(json.dumps(line), flush=True)
    return 0


def report(manifest: dict, cell: Cell, result: dict) -> dict:
    import observe
    import trace_reduce

    device = observe.device_report()
    metrics: dict = {}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device}
    if not cell.trace:
        values = {**result["end_to_end"], "setup_s": result["setup_s"]}
        for m in metrics_of(manifest, "end_to_end", cell.name):
            if m["name"] not in values:
                raise SystemExit(f"{cell.name}: nothing computes the "
                                 f"end-to-end metric {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        return line
    trace = result.get("trace")
    lo = hi = None
    if trace is not None and trace.chips:
        # the traced window is what lies between the benchmark's own marks
        marks = [m.start for m in trace.marks if m.name in (
            "bench:window_open", "bench:window_close", result.get("sync_mark"))]
        if len(marks) >= 2 and max(marks) > min(marks):
            lo, hi = min(marks), max(marks)
    result["trace_window"] = (lo, hi) if lo is not None else None
    if lo is not None:
        used = trace.chips[:cell.chips]
        device["busy_s"] = sum(trace_reduce.busy_seconds(c, lo, hi)
                               for c in used) / len(used)
        device["window_s"] = hi - lo
        line["breakdown"] = trace_reduce.breakdown(
            trace, lo, hi, sync_mark=result.get("sync_mark"))
        result["samples"]["seconds_by_opcode"] = dict(list(
            trace_reduce.seconds_by_opcode(trace.chips[0], lo, hi).items())[:12])
    elif cell.rehearsal:
        device["busy_s"], device["window_s"] = 0.0, result["window_s"]
    else:
        raise SystemExit("the traced run left no device trace to read")
    for m in metrics_of(manifest, "per_layer", cell.name):
        try:
            value = read_layer_metric(m["name"], result, cell)
        except SystemExit:
            if not cell.rehearsal:
                raise
            value = None    # a rehearsal's device has no published peaks
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return line


if __name__ == "__main__":
    sys.exit(main())
