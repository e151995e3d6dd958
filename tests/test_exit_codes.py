"""Exit-code contract (resilience/exit_codes.py): uniqueness of the table."""

from lstm_tensorspark_tpu.resilience import exit_codes as ec


def test_codes_are_unique_and_in_range():
    codes = [ec.USAGE_RC, ec.REGRESSION_RC, ec.LIVENESS_RC, ec.ANOMALY_RC,
             ec.POISON_RC, ec.FAULT_CRASH_RC]
    assert len(set(codes)) == len(codes)  # no collisions, ever again
    assert all(0 < c < 128 for c in codes)  # never masquerade as a signal
    assert ec.RETRYABLE_RCS <= set(codes)
    assert ec.POISON_RC not in ec.RETRYABLE_RCS  # poison means STOP
