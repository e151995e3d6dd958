"""Device-resident LM dataset: corpus staged in HBM, windows sliced on-device.

Reference parity + the TPU-native upgrade: Spark caches the RDD in executor
memory, so per-round the reference moves only params/grads — the *data* stays
resident with the workers (SURVEY.md §3.1). The host-fed JAX path regressed
that: every K-step dispatch ships [K, B, T] token windows from the host,
which for a small model costs more than the step's compute. This module
restores the reference's data-locality property the TPU way:

- the contiguous per-row token streams (`data.batching.lm_windows` layout:
  [B, n_windows*T] inputs + shifted targets) are `device_put` ONCE;
- the train step takes a scalar window index and `lax.dynamic_slice`s the
  [B, T] batch inside the jitted program (one slice per step of the K-step
  scan) — per-dispatch host traffic is one int32 scalar;
- under data parallelism the streams shard over the "data" mesh axis with
  `P("data", None)` — each chip holds only its batch rows, exactly like a
  Spark partition's cached shard; slicing is along time, so no collective
  is ever needed for the feed.

Stream order is identical to `lm_epoch_batches`, so stateful TBPTT carries
stay aligned and host-fed vs device-resident runs are bit-identical
(tests/test_device_data.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .batching import lm_windows


@dataclasses.dataclass(frozen=True)
class DeviceLMData:
    """HBM-staged LM corpus + static window geometry.

    ``arrays`` is a pytree of device arrays passed explicitly through jit
    (never closed over: closure constants can be baked into the executable,
    which would duplicate a large corpus into every compiled program).
    """

    arrays: dict  # {"streams": [B, n_windows*T], "shifted": same} int32
    batch_size: int
    seq_len: int
    n_windows: int

    @property
    def tokens_per_window(self) -> int:
        return self.batch_size * self.seq_len


def _placer(mesh: Mesh | None, spec: P | None = None):
    """One device_put closure for every stager: ``spec`` placement on the
    mesh (replicated when spec is None/P()), default device otherwise."""
    if mesh is None:
        return lambda a: jax.device_put(np.ascontiguousarray(a))
    sharding = NamedSharding(mesh, spec if spec is not None else P())
    return lambda a: jax.device_put(np.ascontiguousarray(a), sharding)


def stage_lm_data(
    tokens: np.ndarray,
    batch_size: int,
    seq_len: int,
    *,
    mesh: Mesh | None = None,
    axis: str = "data",
) -> DeviceLMData:
    """Build the [B, n_windows*T] streams host-side (pure reshape) and place
    them on device — batch rows sharded over ``axis`` when a mesh is given,
    single default device otherwise."""
    streams, shifted, n_windows = lm_windows(tokens, batch_size, seq_len)
    put = _placer(mesh, P(axis, None))
    return DeviceLMData(
        arrays={"streams": put(streams), "shifted": put(shifted)},
        batch_size=batch_size,
        seq_len=seq_len,
        n_windows=n_windows,
    )


def slice_window(arrays: dict, w: jax.Array, seq_len: int) -> dict:
    """Traced: window index (scalar int32) → {"inputs","targets"} [B, T]."""
    s = w * seq_len
    return {
        "inputs": lax.dynamic_slice_in_dim(arrays["streams"], s, seq_len, axis=1),
        "targets": lax.dynamic_slice_in_dim(arrays["shifted"], s, seq_len, axis=1),
    }


def window_index_stream(data: DeviceLMData, steps_per_call: int,
                        *, start_step: int = 0):
    """Host-side iterator of starting window indices, one per K-step dispatch
    (the entire per-call feed). Wraps around epochs forever, matching
    `lm_batch_stream`'s ordering. ``start_step`` fast-forwards to the window
    a resumed run would be at (data-exact resume)."""
    w = start_step % data.n_windows
    while True:
        yield np.int32(w)
        w = (w + steps_per_call) % data.n_windows


def stage_stacked_batches(batches, *, mesh: Mesh | None = None) -> dict:
    """Stack an iterator of equal-shape host batch dicts into ONE
    [n_batches, ...] pytree placed on device (replicated under a mesh) —
    the staging step for fused in-executable eval (train/device_step.py):
    the traced eval scans the leading axis, so the batches must be the
    EXACT ones the host eval loop would see."""
    ev_list = list(batches)
    if not ev_list:
        raise ValueError("stage_stacked_batches: empty batch iterator")
    put = _placer(mesh)
    return {k: put(np.stack([b[k] for b in ev_list])) for k in ev_list[0]}


# ---- generic per-example staging (classification: BASELINE.md config 2) ----


@dataclasses.dataclass(frozen=True)
class DeviceExamples:
    """HBM-staged fixed-shape example arrays ([N, ...] per key), batched
    on-device by row gather. Arrays are placed REPLICATED (every shard can
    gather any row); per-dispatch host traffic is the [K, B] index array."""

    arrays: dict
    num_examples: int


def stage_examples(host_arrays: dict, *, mesh: Mesh | None = None) -> DeviceExamples:
    n = next(iter(host_arrays.values())).shape[0]
    for k, a in host_arrays.items():
        if a.shape[0] != n:
            raise ValueError(
                f"leading dims differ: {k} has {a.shape[0]} rows, expected {n}"
            )
    put = _placer(mesh)
    return DeviceExamples(
        arrays={k: put(a) for k, a in host_arrays.items()}, num_examples=n
    )


def take_batch(arrays: dict, idx: jax.Array) -> dict:
    """Traced: row indices [B] → batch {key: [B, ...]}."""
    return {k: jnp.take(a, idx, axis=0) for k, a in arrays.items()}


# ---- series staging (forecasting: BASELINE.md config 4) ----


@dataclasses.dataclass(frozen=True)
class DeviceSeries:
    """HBM-staged [N, F] time series; (context, horizon) windows are sliced
    on-device from per-example start indices."""

    arrays: dict  # {"series": [N, F]}
    context_len: int
    horizon: int
    num_windows: int


def stage_series(
    series: np.ndarray, context_len: int, horizon: int,
    *, mesh: Mesh | None = None,
) -> DeviceSeries:
    n_windows = len(series) - context_len - horizon + 1
    if n_windows < 1:
        raise ValueError(
            f"series length {len(series)} < context {context_len} + horizon {horizon}"
        )
    put = _placer(mesh)
    return DeviceSeries(
        arrays={"series": put(series.astype(np.float32))},
        context_len=context_len,
        horizon=horizon,
        num_windows=n_windows,
    )


def slice_forecast_batch(
    arrays: dict, starts: jax.Array, context_len: int, horizon: int
) -> dict:
    """Traced: window starts [B] → {"context" [B,C,F], "targets" [B,H,F],
    "valid" [B]} — the exact layout of `batching.forecast_windows`."""
    series = arrays["series"]
    F = series.shape[-1]

    def one(s):
        ctx = lax.dynamic_slice(series, (s, 0), (context_len, F))
        tgt = lax.dynamic_slice(series, (s + context_len, 0), (horizon, F))
        return ctx, tgt

    ctx, tgt = jax.vmap(one)(starts)
    return {
        "context": ctx,
        "targets": tgt,
        "valid": jnp.ones(starts.shape[0], bool),
    }
