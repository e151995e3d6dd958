"""On-TPU test suite — runs on the real chip (NO platform forcing here,
unlike tests/conftest.py which pins the 8-device CPU mesh).

Run: ``python -m pytest tests_tpu/ -q`` on a machine with a TPU attached
(from the sandbox: through the chip tool, one process per chip). Every
module skips itself when no TPU is present, so this directory is safe to
include in any environment. Nothing here probes the backend from a child
process first: a child that touches JAX takes the chip from the process
that needs it.
"""
