"""The yardstick's arithmetic: FLOPs and bytes from shapes, percentiles,
peaks, and that a failed request is a miss."""
import math

import pytest

import flops
import loadgen
import serve_cell


@pytest.mark.parametrize("V,H,L,want", [(50_000, 1024, 4, 508.6e6),
                                        (33_278, 650, 2, 170.3e6)])
def test_train_flops_per_token(V, H, L, want):
    got = flops.lm_train_flops_per_token(V, H, L)
    assert got == 3 * (L * 16 * H * H + 2 * H * V)
    assert got == pytest.approx(want, rel=1e-3)


def test_copy_agrees_with_the_programs_accounting():
    from lstm_tensorspark_tpu.utils import flops as theirs

    assert flops.lm_fwd_flops_per_token(50_000, 1024, 4) == \
        theirs.lm_fwd_flops_per_token(50_000, 1024, 4)
    assert flops.TRAIN_FLOPS_MULTIPLIER == theirs.TRAIN_FLOPS_MULTIPLIER


def test_recurrence_work_is_part_of_the_layers_work():
    B, T, H, L = 64, 128, 1024, 4
    rec = flops.recurrence_train_flops_per_step(B, T, H, L)
    layers = 3 * L * 16 * H * H * B * T
    assert rec == pytest.approx(layers / 3)        # h@U fwd + dh bwd of 6 matmuls
    # bf16 4H-wide streams dominate the bytes; U once per kernel call
    assert flops.recurrence_train_bytes_per_step(B, T, H, L, 2) == \
        L * (B * T * (4 * 4 * H * 2 + 4 * H * 4) + 2 * 4 * H * H * 2)


def test_decode_step_bytes_counts_layers_and_head_once():
    V, H, L = 50_000, 1024, 4
    assert flops.decode_step_bytes(V, H, L, None, 4) == \
        4 * (L * (2 * H * 4 * H + 4 * H) + H * V + V)


def test_percentile_is_linear_interpolation():
    xs = list(range(1, 101))
    assert flops.percentile(xs, 50) == 50.5
    assert flops.percentile(xs, 95) == pytest.approx(95.05)
    assert flops.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        flops.percentile([], 95)


def test_unknown_device_is_an_error_not_a_default():
    assert flops.peaks("TPU v5 lite")["bf16_tflops"] == 197.0
    assert flops.peaks("TPU v5 lite")["hbm_gbytes_per_s"] == 819.0
    with pytest.raises(SystemExit):
        flops.peaks("cpu")


def test_failed_requests_enter_the_tail_as_the_windows_length():
    def outcome(i, ok):
        a = loadgen.Arrival(i, float(i), 4, 4, None, (0, i))
        o = loadgen.Outcome(a, due_at=100.0 + i, ok=ok)
        o.first_token_at = o.due_at + 0.010 if ok else math.nan
        return o

    window = [outcome(i, ok=i % 10 != 0) for i in range(100)]   # 10 misses
    ttft = serve_cell.ttft_samples(window, 30.0)
    assert sorted(ttft)[-10:] == [30.0] * 10
    assert flops.percentile(ttft, 95) == 30.0       # the tail sees them
    assert flops.percentile(ttft, 50) == pytest.approx(0.010)
