"""graftlint fixture: clean twin of viol_exit_code — named constants
from the one exit-code table; messages and exit(0)/main() passthroughs
stay legal."""

import sys

from lstm_tensorspark_tpu.resilience.exit_codes import ANOMALY_RC, LIVENESS_RC


def main():
    return 0


def gate(failed, regression_rc):
    if failed:
        sys.exit(regression_rc)  # named, routed by the caller


def bail(reason):
    raise SystemExit(f"fatal: {reason}")  # message form exits 1


def anomaly_abort():
    raise SystemExit(ANOMALY_RC)


def liveness_exit():
    sys.exit(LIVENESS_RC)


def ok():
    sys.exit(0)  # the universal success constant


if __name__ == "__main__":
    raise SystemExit(main())
