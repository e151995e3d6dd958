"""Every kind of cell end to end at a tiny size on the CPU (four virtual
devices for the data-parallel one), through `tests/rehearse.py`: the last
line of stdout must be the object the contract fixes. And `run.py` itself
must refuse to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def run(script, args, devices=1, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, script, *args], env=env, timeout=timeout,
                          capture_output=True, text=True, cwd=os.path.dirname(BENCH))


with open(os.path.join(HERE, "data", "BENCHMARK.tiny.json")) as f:
    MANIFEST = json.load(f)


def expected(group, workload):
    return {m["name"] for m in MANIFEST[group]
            if workload in m.get("workloads", [workload])}


@pytest.mark.parametrize("workload,devices", [
    ("tiny-train-1chip", 1), ("tiny-serve", 1), ("tiny-train-dp4", 4)])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_prints_the_contracts_last_line(workload, devices, trace):
    p = run(os.path.join(HERE, "rehearse.py"),
            ["--workload", workload, "--seed", str(2 ** 31 + 11), "--seconds", "2",
             "--trace", str(trace)], devices)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["count"] == devices
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if trace:
        # no device plane on the CPU: the trace readers return nothing and
        # their metrics are left out; the counters and clocks are there
        assert set(line["metrics"]) <= expected("per_layer", workload)
        assert "compile_s" in line["metrics"]
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert set(line["metrics"]) == expected("end_to_end", workload)


def test_run_py_refuses_anything_but_a_tpu():
    p = run(os.path.join(BENCH, "run.py"),
            ["--workload", "c5-train-1chip", "--seed", "1", "--seconds", "1"])
    assert p.returncode != 0
    assert "no TPU" in p.stderr and not p.stdout.strip()
