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


import pytest  # noqa: E402


@pytest.fixture(scope="module")
def record_spans(tmp_path_factory):
    """``record_spans(fn)``: run ``fn`` under a `jax.profiler` session with
    the Python tracer off (as `--profile-dir` and the benchmark record) and
    return every event of the trace's host planes, in order of start: dicts
    of ``name``, ``line`` (one per thread), ``start``/``end`` in ns, and for
    the program's spans (``<layer>:<what>``) their ``args``. What ``fn``
    returned is kept as ``record_spans.result``; ``record_spans.read(dir)``
    reads a trace that something else recorded (`--profile-dir`)."""

    def record(fn):
        out = tmp_path_factory.mktemp("profile")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(out), profiler_options=options)
        try:
            record.result = fn()
        finally:
            jax.profiler.stop_trace()
        return read_host_events(str(out))

    record.read = read_host_events
    return record


def read_host_events(profile_dir):
    import glob

    (path,) = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                span = e.name.split(":")[0] in ("serve", "engine", "train")
                events.append({
                    "name": e.name, "line": (plane.name, i),
                    "start": e.start_ns, "end": e.start_ns + e.duration_ns,
                    "args": dict(e.stats) if span else None})
    return sorted(events, key=lambda s: (s["start"], -s["end"]))
