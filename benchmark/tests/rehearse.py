"""Test-only entry: one cell of `tests/data/BENCHMARK.tiny.json` on whatever
device JAX has (the CPU), through the same `run.main`. Never a measurement:
`run.py` itself has no way to run without a TPU."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(sys.argv[1:], rehearsal=True, manifest_path=os.path.join(
        HERE, "data", "BENCHMARK.tiny.json")))
