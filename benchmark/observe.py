"""What the benchmark watches while the program runs: compiles (from JAX's
own monitoring events, as `chip_smoke.Observed` does — the original is listed
in PERF.md, Open questions), the device, and its memory."""

from __future__ import annotations

import time


class Compiles:
    """Every backend compile of this process, cache fetches included, with
    the `time.perf_counter()` instant it ended."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.events: list[tuple[float, float, str]] = []  # (at, seconds, fn)
        self.cache_hits = 0

    def _duration(self, name, secs, fun_name="?", **_):
        if name == self.EVENT:
            self.events.append((time.perf_counter(), secs, fun_name))

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    @property
    def seconds(self) -> float:
        return sum(s for _, s, _ in self.events)

    def between(self, lo: float, hi: float) -> list[str]:
        return [fn for t, _, fn in self.events if lo < t <= hi]


def peak_bytes() -> int:
    """`peak_bytes_in_use` of the fullest device (0 where the backend does
    not report it: the CPU)."""
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use") or 0
               for d in jax.devices())


def device_report() -> dict:
    """The device as JAX reports it; the peak is the fullest chip's."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes()}
