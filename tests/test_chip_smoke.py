"""chip_smoke.py without a chip: it must fail, and its phases — the same
functions the chip run calls — are rehearsed here at tiny sizes on the
CPU (Pallas in interpret mode, the four-chip phases on virtual devices).
On the CPU every phase must RUN to its end and its check must fail on
exactly the kernel it could not have engaged, nothing earlier."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dataclasses.replace(
    chip_smoke.FULL, vocab=302, word_types=300, train_tokens=4000,
    hidden=16, layers=2, batch=8, seq_len=8, small_steps_per_call=2,
    batch_buckets="1,4")


def test_without_a_tpu_it_fails_and_never_says_ok():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    # it stopped at the device check: no later phase ran
    assert [json.loads(l)["phase"] for l in lines[:-1]] == ["device"]


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Corpus + the train phase, once: the serve phases need its
    checkpoint."""
    workdir = str(tmp_path_factory.mktemp("chip_smoke"))
    chip_smoke.write_corpus(os.path.join(workdir, "corpus"), TINY, seed=0)
    result, check = chip_smoke.phase_train(workdir, TINY, 0)
    return workdir, result, check


def test_train_phase(trained):
    _, result, check = trained
    assert result["vocab"] == 302  # every generated type + <pad>/<unk>
    assert len(result["losses"]) == TINY.calls
    # --backend auto takes every device it finds: conftest's 8 virtual
    # ones here, the one chip there
    assert result["state_on"] == list(range(8)) and result["partitions"] == 8
    assert result["programs_compiled"] > 0
    # the DP step reaches XLA once (it used to recompile on its second
    # dispatch: step counter and rng were not placed on the mesh)
    assert result["train_step_compiles"] == {"jit(core)": 1}
    # the run is sound up to the one thing a CPU cannot do
    with pytest.raises(chip_smoke.CheckFailed, match="not the fused Pallas"):
        check()
    assert "this is cpu" in result["recurrence"]
    assert result["recurrence"] in result["recurrence_traced"]
    assert result["tpu_custom_calls"] == 0


def test_serve_selftest_phase(trained):
    workdir, _, _ = trained
    result, check = chip_smoke.phase_serve_selftest(workdir, TINY, 0)
    check()  # the scan window is what config 5 serves on: passes anywhere
    assert result["decode_kernel"] == "scan" and result["mismatches"] == 0
    assert result["replicas"][0]["device"]["platform"] == "cpu"


def test_serve_http_phase(trained):
    workdir, _, _ = trained
    result, check = chip_smoke.phase_serve_http(workdir, TINY, 0)
    check()
    assert result["compiles_during_requests"] == 0
    assert result["compiles_after_warmup"] > 0
    assert result["device"]["platform"] == "cpu"


def test_small_width_phases(tmp_path):
    """The quick-start train run and the default-width selftest: on a TPU
    these are where the small kernels run compiled."""
    result, check = chip_smoke.phase_small_train(str(tmp_path), TINY, 0)
    assert len(result["losses"]) == 3
    with pytest.raises(chip_smoke.CheckFailed, match="not the fused Pallas"):
        check()
    result, check = chip_smoke.phase_small_selftest(str(tmp_path), TINY, 0)
    assert result["rc"] == 0 and result["mismatches"] == 0
    with pytest.raises(chip_smoke.CheckFailed, match="resolved to scan"):
        check()


def test_multichip_dp_phase(trained):
    """Four of the eight virtual devices: DP loss parity, the state on
    four devices, the all-reduce in the lowered step."""
    workdir, _, _ = trained
    result, check = chip_smoke.phase_dp_train(workdir, TINY, 0)
    assert result["partitions"] == 4 and result["state_on"] == [0, 1, 2, 3]
    assert result["max_rel_diff"] <= 5e-3
    assert result["all_reduce_in_lowered_programs"] > 0
    # everything holds but the memory report, which the CPU does not give
    with pytest.raises(chip_smoke.CheckFailed, match="peak bytes"):
        check()


def test_multichip_replicas_phase(tmp_path):
    result, check = chip_smoke.phase_replicas(str(tmp_path), TINY, 0)
    check()
    homes = [r["device"]["cache_on"] for r in result["replicas"]]
    assert sorted(h[0] for h in homes) == [0, 1, 2, 3]
