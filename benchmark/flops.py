"""Operations and bytes, from shapes alone, and the table of peaks.

The yardstick every PR is measured with: it lives here, not in the program,
so a PR that claims a gain cannot move it. `lm_fwd_flops_per_token` and the
x3 training multiplier are a copy of the program's `utils/flops.py` (listed
in PERF.md, Open questions, for a later PR to delete one of the two).

Matmul operations only (the MXU's work): embedding gathers and elementwise
gate math are left out, as is usual for model-FLOPs utilisation. Recomputed
operations (the chunked head's second matmul in the backward, a remat
backward) do not count: MFU is the work the algorithm requires.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

#: backward = dL/dW and dL/dx per forward matmul
TRAIN_FLOPS_MULTIPLIER = 3.0


def peaks(device_kind: str) -> dict:
    """The published peaks of ONE chip of this kind. A device that is not in
    `peaks.json` is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"benchmark/peaks.json has no entry for device kind "
                         f"{device_kind!r}: add its published peaks first")
    return table[device_kind]


def lm_fwd_flops_per_token(V: int, H: int, L: int, E: int | None = None) -> float:
    """Forward matmul FLOPs per token: per layer x@W (2*Din*4H) + h@U
    (2*H*4H), plus the softmax head (2*H*V)."""
    E = E or H
    f = 0.0
    for layer in range(L):
        din = E if layer == 0 else H
        f += 8.0 * H * (din + H)
    return f + 2.0 * H * V


def lm_train_flops_per_token(V: int, H: int, L: int, E: int | None = None) -> float:
    return TRAIN_FLOPS_MULTIPLIER * lm_fwd_flops_per_token(V, H, L, E)


def recurrence_train_flops_per_step(B: int, T: int, H: int, L: int) -> float:
    """What the fused recurrence kernels alone must compute in one optimizer
    step: forward h@U (2*H*4H per token and layer) and the backward's
    dh = dz@U^T (the same again). The input projection and the weight
    cotangents are XLA matmuls outside the kernels and are not counted."""
    return 2.0 * (2.0 * H * 4 * H) * B * T * L


def recurrence_train_bytes_per_step(B: int, T: int, H: int, L: int,
                                    compute_bytes: int) -> float:
    """The least HBM traffic of the kernel boundary the program has today,
    per optimizer step: the 4H-wide streams (xproj in, z residual out and
    back in, dz out) in the compute dtype, the H-wide ones (ys out, cs out
    and back in, dys in) in float32, and U once per kernel call. A plan
    that streams U once per time step moves more than this; the share then
    says so."""
    wide = 4 * H * compute_bytes      # per token: one 4H-wide stream
    narrow = H * 4                    # per token: one H-wide f32 stream
    per_layer = B * T * (4 * wide + 4 * narrow) + 2 * (4 * H * H * compute_bytes)
    return float(L * per_layer)


def decode_step_bytes(V: int, H: int, L: int, E: int | None,
                      param_bytes: int) -> float:
    """Bytes ONE decode step of a batch must read whatever the batch size:
    every layer's W and U and bias, and the whole head (kernel + bias), at
    the size they are stored in. The embedding rows gathered (B rows) and
    the carries are left out: they scale with the batch and are small."""
    E = E or H
    n = 0
    for layer in range(L):
        din = E if layer == 0 else H
        n += (din + H) * 4 * H + 4 * H
    n += H * V + V
    return float(n * param_bytes)


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics — numpy's default, written out so the arithmetic is here."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))
