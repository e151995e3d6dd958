"""CPU-testable pieces of the benchmark harness (bench.py): the
strategy-aware implementation bound must track the runtime's own backward
gate for every table config."""

import sys
import os

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_impl_bound_tracks_runtime_strategy_per_config():
    """impl_bwd_strategy comes from chosen_bwd_strategy at each config's
    layer-0 shape; the serialized pass count is layers x dirs x (1 + the
    strategy's in-chain multiplier). Pin today's five configs so a cost-
    model change that silently flips a plan shows up here, not only in a
    stale table."""
    import bench

    rl = {"chain_sec": 1e-4, "chain_flops": 1e9}
    rec = {"train_flops_step": 1e10}
    want = {
        "ptb_char": ("resident", 2),       # L=1, uni, stored-z bwd
        # L=1, bi: BOTH directions advance in the stacked-direction kernel
        # (ops/pallas_bilstm.py) — one serialized residentx chain
        "imdb_bilstm": ("residentx", 3),
        # r4 chunk-flexible planning (pallas_lstm._plan_bwd): resident is
        # tried at chunks 8/4/2/1 before falling through to tiled, and the
        # bf16 residual streams (_rbytes) halve the streamed-block VMEM, so
        # H=650/1024 (padded 768/1024) now fit U^T resident where they
        # previously spilled to tiled. Hardware caveat: at H=1024 U^T alone
        # is ~8.4 MiB bf16 against the 12 MiB budget — it compiles for a
        # described v5e (tests/test_chip_compile.py) and ran on the chip in
        # chip_smoke.py (PR 22); whether it WINS is an A/B still owed.
        "wikitext2": ("resident", 4),      # L=2, uni, U^T resident (r4 flip)
        "uci_seq2seq": ("resident", 4),    # L=2 (dU hoist refit resident)
        "wikitext103": ("resident", 8),    # L=4, uni, U^T resident (r4 flip)
    }
    for name, (strategy, passes) in want.items():
        out = bench._impl_bound(name, dict(rl), rec, measured=1e-3)
        assert out["impl_bwd_strategy"] == strategy, (name, out)
        assert out["impl_serial_passes"] == passes, (name, out)
        # bound = passes * chain + parallel remainder, vs UNROUNDED measured
        parallel = max(1e10 - passes * 1e9, 0.0) / (bench.PEAK_TFLOPS * 1e12)
        assert out["impl_bound_sec_per_step"] == pytest.approx(
            passes * 1e-4 + parallel, abs=1.5e-6)


def test_impl_bound_bidir_fuse_lever(monkeypatch):
    """LSTM_TSP_NO_BIDIR_FUSE=1 must restore the two-serialized-scans
    model for the classifier — the bound follows the SAME lever the
    runtime dispatch honors, so A/B numbers get matching bounds."""
    import bench

    monkeypatch.setenv("LSTM_TSP_NO_BIDIR_FUSE", "1")
    out = bench._impl_bound(
        "imdb_bilstm", {"chain_sec": 1e-4, "chain_flops": 1e9},
        {"train_flops_step": 1e10}, measured=1e-3)
    assert out["impl_bwd_strategy"] == "residentx"
    assert out["impl_serial_passes"] == 6


def test_impl_bound_heterogeneous_scans_report_mixed(monkeypatch):
    """ADVICE r3: a config whose scans plan DIFFERENT strategies must not
    inherit the layer-0 label. A long-context seq2seq (encoder T >= the
    fusedx threshold, horizon 24) plans residentx encoders + resident
    decoders: the label goes 'mixed', per-strategy counts are published,
    and the serialized steps weight each scan by its own length."""
    import bench

    cfgs = dict(bench.CONFIGS)
    cfgs["long_seq2seq"] = dict(kind="seq2seq", F=370, H=256, L=2, B=64,
                                T=300, horizon=24)
    monkeypatch.setattr(bench, "CONFIGS", cfgs)
    out = bench._impl_bound(
        "long_seq2seq", {"chain_sec": 1e-4, "chain_flops": 1e9},
        {"train_flops_step": 1e10}, measured=1e-3)
    assert out["impl_bwd_strategy"] == "mixed"
    assert out["impl_bwd_strategies"] == {"residentx": 2, "resident": 2}
    # 2 encoder scans: 300*(1+2); 2 decoder scans: 24*(1+1)
    assert out["impl_serial_steps"] == 2 * 300 * 3 + 2 * 24 * 2
    assert out["impl_serial_passes"] == pytest.approx(1896 / 324, abs=1e-4)


def test_bench_prints_no_result_without_the_chip():
    """bench.py measures one TPU v5 lite: on anything else it must exit
    non-zero having printed NO record — a number from another device may
    never appear under its metric names."""
    import subprocess
    import sys as _sys

    out = subprocess.run(
        [_sys.executable, "bench.py"], capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU v5 lite" in out.stderr and "No result" in out.stderr
