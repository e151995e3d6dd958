"""Test fixtures: run everything on a virtual 8-device CPU mesh.

The moral equivalent of the reference's Spark ``local[N]`` story
(SURVEY.md §4): distributed topology simulated on one host. Must set env
before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
# cli.main places the persistent compilation cache (utils/compile_cache.py)
# and many tests call it in-process: keep the cache itself off here, so a
# test run neither depends on nor fills <checkout>/.jax_cache
jax.config.update("jax_enable_compilation_cache", False)

assert jax.device_count() == 8, jax.devices()
