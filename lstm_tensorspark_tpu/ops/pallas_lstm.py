"""Fused Pallas TPU kernels for the LSTM recurrence.

Motivation (SURVEY.md §2 native-capability table: "optional Pallas kernel
for the fused cell if XLA fusion is insufficient"): under `lax.scan` XLA
executes T small programs, each round-tripping h/c and the gate activations
through HBM. These kernels run the WHOLE sequence in one `pallas_call`:

- the input projection ``X @ W + b`` for all T steps is hoisted OUT of the
  recurrence into one large MXU matmul (XLA does this part best);
- the serial part — ``z_t = Xproj_t + h @ U``, gates, state update — runs
  over a sequential grid with h and c RESIDENT IN VMEM scratch (TPU grids
  execute in order, so scratch carries state between steps).

Two kernel strategies, chosen by a single VMEM cost model (`_plan_fwd` /
`_plan_bwd` — both gates derive from the same per-buffer accounting):

- **resident** (small H): the recurrent matrix U lives in VMEM for the whole
  sequence; the grid is time-chunked (``chunk`` steps python-unrolled per
  grid step). Minimum HBM traffic.
- **tiled** (big H, e.g. configs 3/5 at H=650/1024): U cannot fit VMEM, so
  the grid is ``(T, K)`` with U streamed in K row-tiles per step and the
  pre-gate activations accumulated f32 in a full-width VMEM scratch; h is
  kept twice (tile-major for the matmul reads, full-width for the update).
  U streams from HBM once per step — the same per-step U traffic `lax.scan`
  pays — while still deleting the scan's h/c round-trips and per-step
  dispatch overhead.

Hidden sizes that are not lane-aligned (H % 128 != 0, e.g. 650) are
zero-PADDED to the next multiple of 128 per gate block. Padding is exactly
gradient-neutral: padded U/W columns and biases are zero, so padded
pre-activations are z=0, padded gates are (i,f,o)=σ(0)=½, g=tanh(0)=0, and
padded h/c lanes stay exactly 0 through the whole recurrence; all padded
cotangents vanish identically (dz_pad = 0), so sliced gradients equal the
unpadded ones. The pad/slice lives OUTSIDE the custom VJP, so JAX transposes
it automatically.

Variable-length and bidirectional support (the bi-LSTM / seq2seq configs):

- ``mask`` ([B, T] bool) freezes the carry at padded steps exactly as in
  `lstm_scan`: the kernels stream a lane-broadcast f32 mask and blend
  ``m*new + (1-m)*old`` into h and c. The backward applies the transposed
  blend: the skipped cotangent ``(1-m)*dh`` bypasses the gate algebra into
  the previous step.
- ``reverse`` is implemented by flipping the time axis OUTSIDE the custom
  VJP (inputs and mask in, outputs back), so the kernels always run
  forward-in-time and autodiff transposes the flips for free. The flip is a
  strided HBM read XLA fuses into the input projection.

Training support: `pallas_lstm_scan` carries a custom VJP with THREE
backward strategies:
- **resident fused BPTT** (`_lstm_bwd_kernel`): reverse sequential grid with
  dh/dc carries resident in VMEM, consuming the z/c trajectories the
  train-mode forward streams out; the cell state c_t is RECOMPUTED from
  (z_t, c_{t-1}) in-kernel — bit-identical in f32 — so the backward
  streams one fewer [T,B,H] tensor than a save-everything design;
- **tiled fused BPTT** (`_lstm_bwd_tiled_kernel`): the sequential kernel
  computes only dz (streaming U in column tiles for the dh carry);
- in EVERY strategy the weight cotangents dU/dW/db and dxs are single
  large MXU matmuls OUTSIDE the kernel (XLA's job — they contract over
  T·B at once; an in-kernel dU accumulate would serialize one more MXU
  op with the reverse dependent chain, measured real time on v5e);
- **recompute fallback** (when `remat_chunk` is set — memory priority — or
  the O(T) f32 residuals would exceed `_RESIDUAL_HBM_BUDGET`, or no fused
  kernel fits): re-run the pure-jax scan under `jax.vjp` (remat-style),
  bit-exact with the reference BPTT.

Tiling constraints (pallas_guide.md): last dim 128 lanes; float32 sublane 8.
`supported()` gates on B % 8 == 0 plus the cost model; callers fall back to
`lstm_scan` otherwise.

Every kernel has a fixed ``name=`` with the stem ``lstm_rec_``
(``fwdx``, ``fwd``, ``fwd_tiled``, ``bwdx``, ``bwd``, ``bwd_tiled``): the
compiled step's custom calls, and so a profile's operations, are labelled
with it (``lstm_rec_fwd_tiled.24``), which is how the recurrence's device
time is found (docs/OPERATIONS.md).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lstm_cell import LSTMParams, fuse_params
from .scan import lstm_scan


_VMEM_BUDGET = 12 * 2**20  # bytes; conservative vs ~16 MiB/core
_LANE = 128
# The fused backward saves O(T) f32 residuals (z [T,B,4H] + cs [T,B,H]) in
# HBM. Above this budget the recompute backward is selected instead — the
# memory/speed trade ADVICE.md flagged, now an explicit heuristic
# (override with LSTM_TSP_RESIDUAL_HBM_MB).
_RESIDUAL_HBM_BUDGET = int(os.environ.get("LSTM_TSP_RESIDUAL_HBM_MB", 4096)) * 2**20
# The fully-fused residentx strategy trades the [T,B,4H] xproj/z HBM
# round-trips for in-kernel projection matmuls serialized with the chain.
# Measured on v5e: +28% at T=400 (config 2), −3% at T=64..192 (configs
# 1/4) — the traffic saved scales with T while the serialization cost is
# per-step. Only prefer it for long sequences (tests override to 0).
_FUSEDX_MIN_T = 256


def _pad_to_lane(h: int) -> int:
    return h + (-h % _LANE)


def _residual_dtype(kernel_dtype):
    """Dtype of the big [*, 4H] HBM streams (xproj in, z residual, dz out).

    r4 bandwidth analysis (DESIGN.md): at config-1 class shapes one
    optimizer step moves ~40 copies of T·B·H·4 bytes through HBM when
    every stream is f32 — more than the chip's HBM bandwidth over the
    measured step time, i.e. these configs are STREAM-bound, not
    chain-bound, and that is the missing ~2x between the measured step
    and the chain-latency roofline. Storing the 4H-wide streams in the
    compute dtype halves the dominant traffic. The cell state (cs),
    carries, and ys stay f32 (the recurrence trajectory's precision);
    gate math still runs f32 in-kernel — only the STORED copies round.
    f32 compute keeps f32 streams (bit-exact parity tests unchanged);
    LSTM_TSP_RESIDUAL_F32=1 forces f32 streams under bf16 compute (the
    A/B lever for measuring the saving)."""
    if (kernel_dtype == jnp.bfloat16
            and os.environ.get("LSTM_TSP_RESIDUAL_F32") != "1"):
        return jnp.bfloat16
    return jnp.float32


def _rbytes(pbytes: int) -> int:
    """Cost-model mirror of `_residual_dtype` (pbytes encodes the kernel
    dtype: 2 = bf16, 4 = f32)."""
    if pbytes == 2 and os.environ.get("LSTM_TSP_RESIDUAL_F32") != "1":
        return 2
    return 4


def _dot_ut(dz, u_ref):
    """``dz @ U^T`` with U read as it is stored ([H, 4H], or a column tile
    of it): dz's gate axis contracts with U's SECOND axis, f32 accumulate."""
    return jax.lax.dot_general(
        dz.astype(u_ref.dtype), u_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Unified VMEM cost model. Every supported()/strategy decision reads these
# four functions; there is no second, implicit accounting (ADVICE.md #1).
# Streamed blocks are counted ×2 for the pipeline's double-buffering.
# ---------------------------------------------------------------------------


def _residentx_fwd_vmem(B: int, H: int, Dp: int, pbytes: int,
                        save_c: bool, has_mask: bool = False,
                        c: int = 8) -> int:
    """Fully-fused resident forward: W AND U live in VMEM, the input
    projection happens in-kernel (one chunk-batched MXU matmul per grid
    step), and nothing but ys/cs ever leaves — the [T,B,4H] xproj and z
    arrays the hoisted variants round-trip through HBM do not exist.
    ``c`` is the time chunk — the planner shrinks it when the streamed
    blocks would not fit at 8."""
    r = _rbytes(pbytes)
    v = 4 * H * H * pbytes  # U resident
    v += Dp * 4 * H * pbytes  # W resident
    v += 4 * H * 4  # bias
    v += 2 * c * B * Dp * r  # xs blocks (double-buffered, stream dtype)
    v += c * B * 4 * H * 4  # in-kernel zx chunk (live value)
    v += 2 * c * B * H * 4  # ys out blocks
    v += 6 * B * H * 4  # h0/c0 in, hT/cT out, h/c scratch
    if has_mask:
        v += 2 * c * B * _LANE * 4  # mask blocks
    if save_c:
        v += 2 * c * B * H * 4  # cs out blocks (the ONLY residual)
    return v


def _residentx_bwd_vmem(B: int, H: int, Dp: int, pbytes: int,
                        has_mask: bool = False, c: int = 8) -> int:
    """Recompute-z fused BPTT: z_t is rebuilt in-kernel from the streamed
    xs/h_prev (W, U resident) instead of being read back from HBM — the
    forward never saved it. ``c`` as in `_residentx_fwd_vmem`."""
    r = _rbytes(pbytes)
    streamed = (
        c * B * Dp * r  # xs blocks (stream dtype)
        + c * B * 4 * H * r  # dz out blocks (stream dtype)
        + c * B * H * 4 * 3  # dys/c_prev/h_prev blocks
    )
    if has_mask:
        streamed += c * B * _LANE * 4  # mask blocks
    return (
        2 * 4 * H * H * pbytes  # U resident (both matmuls read it); the x2
                                # is margin: the chunk plans measured on
                                # the chip were chosen at this count
        + Dp * 4 * H * pbytes  # W resident
        + 4 * H * 4  # bias
        + c * B * 4 * H * 4  # in-kernel zx chunk (live value)
        + streamed * 2  # double-buffered pipelining
        + 4 * B * H * 4  # dh/dc scratch + dh0/dc0 out
    )  # (dU lives outside: contracted from the streamed dz, no accumulator)


def _resident_fwd_vmem(B: int, H: int, pbytes: int, save_residuals: bool,
                       has_mask: bool = False, c: int = 8) -> int:
    """``c`` is the time chunk — r4: the planner shrinks it when the
    streamed blocks would not fit at 8 (previously resident was
    evaluated at the worst-case chunk only, so H=650/1024 fell through
    to the tiled strategy and paid its per-timestep U re-stream — the
    dominant cost the bandwidth analysis exposed; a smaller chunk trades
    some grid-step overhead for keeping U resident)."""
    r = _rbytes(pbytes)
    v = 4 * H * H * pbytes  # U resident
    v += 2 * c * B * 4 * H * r  # xproj blocks (double-buffered, stream dtype)
    v += 2 * c * B * H * 4  # ys out blocks
    v += 6 * B * H * 4  # h0/c0 in, hT/cT out, h/c scratch
    if has_mask:
        v += 2 * c * B * _LANE * 4  # mask blocks
    if save_residuals:
        v += 2 * c * B * 4 * H * r  # z out blocks (stream dtype)
        v += 2 * c * B * H * 4  # cs out blocks
    return v


def _resident_bwd_vmem(B: int, H: int, pbytes: int,
                       has_mask: bool = False, c: int = 8) -> int:
    """``c`` as in `_resident_fwd_vmem` (r4 chunk-flexible planning)."""
    r = _rbytes(pbytes)
    streamed = (
        c * B * 4 * H * r * 2  # z in + dz out blocks (stream dtype)
        + c * B * H * 4 * 2  # dys/c_prev blocks (c_t recomputed; h_prev
                             # not read — dU is contracted outside)
    )
    if has_mask:
        streamed += c * B * _LANE * 4  # mask blocks
    return (
        4 * H * H * pbytes  # U resident
        + streamed * 2  # double-buffered pipelining
        + 4 * B * H * 4  # dh/dc scratch + dh0/dc0 out
    )


def _tiled_fwd_vmem(B: int, H: int, pbytes: int, save_residuals: bool,
                    htile: int, has_mask: bool = False) -> int:
    r = _rbytes(pbytes)
    v = 2 * htile * 4 * H * pbytes  # U row-tile (streamed every step)
    v += 2 * B * 4 * H * r  # xproj block (stream dtype)
    v += B * 4 * H * 4  # z accumulator scratch (f32)
    v += 2 * B * H * 4  # h tiles scratch + c scratch
    v += 2 * B * H * 4  # ys out block
    v += 4 * B * H * 4  # h0/c0 in, hT/cT out
    if has_mask:
        v += 2 * B * _LANE * 4  # mask block
    if save_residuals:
        v += 2 * B * 4 * H * r  # z out block (stream dtype)
        v += 2 * B * H * 4  # cs out block
    return v


def _tiled_bwd_vmem(B: int, H: int, pbytes: int, ttile: int,
                    has_mask: bool = False) -> int:
    r = _rbytes(pbytes)
    v = 2 * H * ttile * pbytes  # U column-tile
    v += 2 * B * 4 * H * r  # z in block (stream dtype)
    v += 2 * 2 * B * H * 4  # dys/c_prev in blocks (c_t recomputed)
    v += 2 * B * 4 * H * r  # dz out block (stream dtype)
    v += B * 4 * H * 4  # dz tiles scratch
    v += 3 * B * H * 4  # dh/dc/dh-accumulator scratch
    v += 4 * B * H * 4  # dhT/dcT in, dh0/dc0 out
    if has_mask:
        v += 2 * B * _LANE * 4  # mask block
        v += B * H * 4  # dh-skip scratch
    return v


def _plan_fwd(B: int, H: int, pbytes: int, *, save_residuals: bool,
              has_mask: bool = False,
              Dp: int | None = None) -> tuple[str, int] | None:
    """(strategy, htile) for the forward kernel at PADDED hidden size H,
    or None when nothing fits. Preference order = least HBM traffic:
    fully-fused residentx (needs the padded input width ``Dp``; with
    residuals it saves cs ONLY — callers must pair it with the residentx
    backward), then hoisted-projection resident, then the largest feasible
    U row-tile."""
    if Dp is not None:
        for c in (8, 4, 2, 1):
            if _residentx_fwd_vmem(B, H, Dp, pbytes, save_residuals,
                                   has_mask, c) <= _VMEM_BUDGET:
                return ("residentx", c)
    # resident at ANY feasible chunk before tiled (r4): a chunk-1 resident
    # kernel reads U once per pallas_call; tiled re-streams U every
    # timestep — T x 4H x H x pbytes of pure HBM traffic per scan
    for c in (8, 4, 2, 1):
        if _resident_fwd_vmem(B, H, pbytes, save_residuals, has_mask,
                              c) <= _VMEM_BUDGET:
            return ("resident", c)
    for htile in (512, 256, 128):
        if H % htile == 0 and _tiled_fwd_vmem(
                B, H, pbytes, save_residuals, htile, has_mask) <= _VMEM_BUDGET:
            return ("tiled", htile)
    return None


def _plan_bwd(B: int, H: int, pbytes: int, has_mask: bool = False,
              Dp: int | None = None) -> tuple[str, int] | None:
    """(strategy, ttile) for the fused backward kernel, or None → recompute
    fallback. ttile tiles U's gate (4H) dim. The residentx strategy
    (recompute-z) is only offered when the matching residentx FORWARD also
    fits — its cs-only residual contract requires the pair."""
    if Dp is not None and _residentx_fwd_vmem(
            B, H, Dp, pbytes, True, has_mask, 1) <= _VMEM_BUDGET:
        for c in (8, 4, 2, 1):
            if _residentx_bwd_vmem(B, H, Dp, pbytes, has_mask,
                                   c) <= _VMEM_BUDGET:
                return ("residentx", c)
    # resident at any feasible chunk before tiled (see _plan_fwd's note);
    # the MATCHING residual-saving forward must also fit, else the pair
    # would plan inconsistently (fwd tiled + bwd resident is fine — both
    # consume/produce the same z/cs streams — but prefer coherent pairs)
    for c in (8, 4, 2, 1):
        if _resident_bwd_vmem(B, H, pbytes, has_mask, c) <= _VMEM_BUDGET:
            return ("resident", c)
    for ttile in (1024, 512, 256, 128):
        if (4 * H) % ttile == 0 and _tiled_bwd_vmem(
                B, H, pbytes, ttile, has_mask) <= _VMEM_BUDGET:
            return ("tiled", ttile)
    return None


def _residual_bytes(T: int, B: int, H: int, bwd_strategy: str = "resident",
                    pbytes: int = 4) -> int:
    if bwd_strategy == "residentx":
        return T * B * H * 4  # cs only (z recomputed in-kernel), f32
    # z [T,B,4H] in the stream dtype + cs [T,B,H] f32
    return T * B * H * (4 * _rbytes(pbytes) + 4)


def chosen_bwd_strategy(B: int, T: int, H: int, pbytes: int, *,
                        has_mask: bool = False, Dp: int | None = None,
                        remat_chunk: int | None = None) -> str:
    """The SINGLE backward-strategy decision: which gradient path a
    `pallas_lstm_scan` at PADDED hidden size ``H`` (and padded input width
    ``Dp``, None when the xproj is hoisted) will actually take —
    ``"residentx"`` / ``"resident"`` / ``"tiled"`` fused kernels, or
    ``"recompute"`` (the pure-jax remat fallback). `_scan_core_fwd` reads
    THIS function, and `tests/test_pallas.py` pins its answer at the
    published shapes. Gates, in order: remat_chunk is the explicit memory-priority
    signal; a backward kernel must plan; its O(T) residuals must fit the
    HBM budget; and the matching residual-saving forward must also fit
    (residentx bwd consumes the residentx fwd's cs-only residuals; the
    legacy bwds need z, so their fwd must not take the fusedx path)."""
    plan_b = _plan_bwd(B, H, pbytes, has_mask, Dp)
    if remat_chunk is not None or plan_b is None:
        return "recompute"
    fusedx = plan_b[0] == "residentx"
    ok = (
        _residual_bytes(T, B, H, plan_b[0], pbytes) <= _RESIDUAL_HBM_BUDGET
        and _plan_fwd(B, H, pbytes, save_residuals=True, has_mask=has_mask,
                      Dp=Dp if fusedx else None) is not None
    )
    return plan_b[0] if ok else "recompute"


def supported(
    batch: int,
    hidden: int,
    platform: str | None = None,
    *,
    param_dtype_bytes: int = 4,
    has_mask: bool = False,
) -> bool:
    """Can a fused kernel run these shapes on this platform?

    Hidden sizes are padded to the 128-lane multiple internally, so any H is
    lane-feasible; the gate is batch sublane alignment (B % 8) plus the VMEM
    cost model (`_plan_fwd`) at the padded size — H=650/1024 now plan onto
    the tiled kernel instead of falling back to lstm_scan. ``has_mask``
    accounts for the streamed mask operand of variable-length scans.
    """
    if platform is None:
        platform = jax.default_backend()
    hp = _pad_to_lane(hidden)
    return (
        platform == "tpu"
        and batch % 8 == 0
        and hidden >= 1
        and _plan_fwd(batch, hp, param_dtype_bytes,
                      save_residuals=False, has_mask=has_mask) is not None
    )


# ---------------------------------------------------------------------------
# Fully-fused resident kernels (W AND U in VMEM; xproj in-kernel; the
# backward RECOMPUTES z — neither xproj nor z ever exists in HBM)
# ---------------------------------------------------------------------------


def _lstm_fwdx_kernel(*refs, hidden: int, dpad: int, chunk: int,
                      save_c: bool, has_mask: bool):
    """Fully-fused forward: per grid step, ONE chunk-batched MXU matmul
    ``[C·B, Dp] @ [Dp, 4H]`` projects the whole chunk's inputs into a live
    VMEM value, then the sequential sub-steps add ``h @ U`` and the gates.
    With ``save_c`` only the cell states stream out (the residentx
    backward's sole residual); z is never materialised."""
    n_in = 6 + has_mask
    xs_ref, w_ref, b_ref, u_ref, h0_ref, c0_ref = refs[:6]
    mask_ref = refs[6] if has_mask else None
    ys_ref, hT_ref, cT_ref = refs[n_in:n_in + 3]
    rest = refs[n_in + 3:]
    if save_c:
        cs_ref, h_scr, c_scr = rest
    else:
        h_scr, c_scr = rest
    t = pl.program_id(0)
    T = pl.num_programs(0)
    H = hidden

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    zx = jnp.dot(
        xs_ref[:].reshape(-1, dpad).astype(w_ref.dtype), w_ref[:],
        preferred_element_type=jnp.float32,
    ) + b_ref[:]
    zx = zx.reshape(chunk, -1, 4 * H)
    h = h_scr[:]
    c = c_scr[:]
    for s in range(chunk):
        z = zx[s] + jnp.dot(
            h.astype(u_ref.dtype), u_ref[:], preferred_element_type=jnp.float32
        )
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H : 2 * H])
        g = jnp.tanh(z[:, 2 * H : 3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H :])
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        if has_mask:
            m = mask_ref[s][:, :1]
            c = m * c_new + (1.0 - m) * c
            h = m * h_new + (1.0 - m) * h
        else:
            c = c_new
            h = h_new
        ys_ref[s] = h
        if save_c:
            cs_ref[s] = c
    h_scr[:] = h
    c_scr[:] = c

    @pl.when(t == T - 1)
    def _():
        hT_ref[:] = h
        cT_ref[:] = c


def _lstm_bwdx_kernel(*refs, hidden: int, dpad: int, chunk: int,
                      has_mask: bool):
    """Recompute-z fused BPTT: the forward saved ONLY the cell states; this
    kernel rebuilds ``z_t = x_t@W + b + h_{t-1}@U`` in-kernel (chunk-batched
    x@W, per-step h_prev@U — bit-identical to the forward's f32 values) and
    runs the same reverse cotangent algebra as `_lstm_bwd_kernel`. Costs one
    extra matmul per step; deletes the [T,B,4H] z round-trip entirely.

    The weight cotangent dU = Σ_t h_{t-1}^T dz_t is NOT accumulated here:
    dz streams out anyway, so `_pallas_backward` contracts it against
    h_prev over all T·B in one large MXU matmul outside — the same split
    the tiled backward uses. That keeps the sequential chain to two MXU
    ops per step (z recompute, dh carry) instead of three — the per-step
    accumulate serialized real MXU issue slots with the chain."""
    n_in = 9 + has_mask
    xs_ref, dys_ref, cprev_ref, hprev_ref = refs[:4]
    mask_ref = refs[4] if has_mask else None
    w_ref, b_ref, u_ref, dhT_ref, dcT_ref = refs[4 + has_mask:n_in]
    dz_ref, dh0_ref, dc0_ref = refs[n_in:n_in + 3]
    dh_scr, dc_scr = refs[n_in + 3:]
    t = pl.program_id(0)
    T = pl.num_programs(0)
    H = hidden

    @pl.when(t == 0)
    def _():
        dh_scr[:] = dhT_ref[:]
        dc_scr[:] = dcT_ref[:]

    zx = jnp.dot(
        xs_ref[:].reshape(-1, dpad).astype(w_ref.dtype), w_ref[:],
        preferred_element_type=jnp.float32,
    ) + b_ref[:]
    zx = zx.reshape(chunk, -1, 4 * H)
    dh = dh_scr[:]
    dc = dc_scr[:]
    for s in range(chunk - 1, -1, -1):
        z = zx[s] + jnp.dot(
            hprev_ref[s].astype(u_ref.dtype), u_ref[:],
            preferred_element_type=jnp.float32,
        )
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H : 2 * H])
        g = jnp.tanh(z[:, 2 * H : 3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H :])
        c_prev = cprev_ref[s]
        tc = jnp.tanh(f * c_prev + i * g)  # tanh(c_new), recomputed
        dh_tot = dh + dys_ref[s]
        dc_in = dc
        if has_mask:
            m = mask_ref[s][:, :1]
            dh_eff = m * dh_tot
            dc_eff = m * dc_in
        else:
            dh_eff = dh_tot
            dc_eff = dc_in
        dc_new = dc_eff + dh_eff * o * (1.0 - tc * tc)
        do = dh_eff * tc * o * (1.0 - o)
        di = dc_new * g * i * (1.0 - i)
        df = dc_new * c_prev * f * (1.0 - f)
        dg = dc_new * i * (1.0 - g * g)
        dz = jnp.concatenate([di, df, dg, do], axis=1)  # [B, 4H] f32
        dz_ref[s] = dz.astype(dz_ref.dtype)  # stored in the stream dtype
        dh = _dot_ut(dz, u_ref)
        dc = dc_new * f
        if has_mask:
            # frozen fraction of the cotangents bypasses the gates
            dh = dh + (1.0 - m) * dh_tot
            dc = dc + (1.0 - m) * dc_in
    dh_scr[:] = dh
    dc_scr[:] = dc

    @pl.when(t == T - 1)
    def _():
        dh0_ref[:] = dh
        dc0_ref[:] = dc


# ---------------------------------------------------------------------------
# Resident kernels (U lives in VMEM; time-chunked grid)
# ---------------------------------------------------------------------------


def _lstm_kernel(*refs, hidden: int, chunk: int, save_residuals: bool,
                 has_mask: bool):
    """Forward recurrence. With ``save_residuals`` the kernel additionally
    streams out the gate pre-activations z_t and cell states c_t — the
    residuals `_lstm_bwd_kernel` consumes (no recompute in the backward).
    With ``has_mask`` a lane-broadcast f32 mask freezes h/c at padded
    steps (carry blend ``m*new + (1-m)*old``, matching `lstm_scan`)."""
    n_in = 4 + has_mask
    xproj_ref, u_ref, h0_ref, c0_ref = refs[:4]
    mask_ref = refs[4] if has_mask else None
    ys_ref, hT_ref, cT_ref = refs[n_in:n_in + 3]
    rest = refs[n_in + 3:]
    if save_residuals:
        z_ref, cs_ref, h_scr, c_scr = rest
    else:
        h_scr, c_scr = rest
    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    H = hidden
    h = h_scr[:]
    c = c_scr[:]
    # ``chunk`` sequential time-steps per grid step (python-unrolled): the
    # per-grid-step overhead (block index bookkeeping, DMA setup) amortises
    # over the chunk while h/c stay in registers/VMEM between sub-steps.
    for s in range(chunk):
        z = xproj_ref[s].astype(jnp.float32) + jnp.dot(
            h.astype(u_ref.dtype), u_ref[:], preferred_element_type=jnp.float32
        )
        if save_residuals:
            z_ref[s] = z.astype(z_ref.dtype)  # stored in the stream dtype
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H : 2 * H])
        g = jnp.tanh(z[:, 2 * H : 3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H :])
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        if has_mask:
            m = mask_ref[s][:, :1]  # [B, 1] f32, lane-broadcasts against H
            c = m * c_new + (1.0 - m) * c
            h = m * h_new + (1.0 - m) * h
        else:
            c = c_new
            h = h_new
        ys_ref[s] = h
        if save_residuals:
            cs_ref[s] = c
    h_scr[:] = h
    c_scr[:] = c

    @pl.when(t == T - 1)
    def _():
        hT_ref[:] = h
        cT_ref[:] = c


def _chunk_for(T: int, cap: int) -> int:
    """Largest chunk ≤ the planner's VMEM-feasible cap that divides T."""
    for c in (8, 4, 2):
        if c <= cap and T % c == 0:
            return c
    return 1


def _lstm_bwd_kernel(*refs, hidden: int, chunk: int, has_mask: bool):
    """Fused BPTT: reverse sequential grid; dh/dc carries live in VMEM
    scratch across grid steps. Per time-step: gate recompute from saved z
    (VPU), cell-state recompute ``c_t = f*c_{t-1} + i*g`` (bit-identical
    f32 — saves streaming c_t), cotangent algebra (VPU), and ONE MXU
    matmul — dz @ U^T for the carry. dU is contracted outside the kernel
    from the streamed dz (see `_lstm_bwdx_kernel`'s note). With
    ``has_mask`` the frozen fraction of the incoming cotangents bypasses
    the gate algebra straight into the previous step (the transpose of
    the forward's carry blend). h_prev is not read at all — it only ever
    fed the dU accumulate — so that input stream is gone too."""
    n_in = 6 + has_mask
    z_ref, dys_ref, cprev_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    u_ref, dhT_ref, dcT_ref = refs[3 + has_mask:n_in]
    dz_ref, dh0_ref, dc0_ref = refs[n_in:n_in + 3]
    dh_scr, dc_scr = refs[n_in + 3:]
    t = pl.program_id(0)
    T = pl.num_programs(0)
    H = hidden

    @pl.when(t == 0)
    def _():
        dh_scr[:] = dhT_ref[:]
        dc_scr[:] = dcT_ref[:]

    dh = dh_scr[:]
    dc = dc_scr[:]
    for s in range(chunk - 1, -1, -1):
        z = z_ref[s].astype(jnp.float32)
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H : 2 * H])
        g = jnp.tanh(z[:, 2 * H : 3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H :])
        c_prev = cprev_ref[s]
        tc = jnp.tanh(f * c_prev + i * g)  # tanh(c_new), recomputed
        dh_tot = dh + dys_ref[s]
        dc_in = dc  # incoming dc carry at step t (pre-mask split)
        if has_mask:
            m = mask_ref[s][:, :1]
            dh_eff = m * dh_tot
            dc_eff = m * dc_in
        else:
            dh_eff = dh_tot
            dc_eff = dc_in
        dc_new = dc_eff + dh_eff * o * (1.0 - tc * tc)
        do = dh_eff * tc * o * (1.0 - o)
        di = dc_new * g * i * (1.0 - i)
        df = dc_new * c_prev * f * (1.0 - f)
        dg = dc_new * i * (1.0 - g * g)
        dz = jnp.concatenate([di, df, dg, do], axis=1)  # [B, 4H] f32
        dz_ref[s] = dz.astype(dz_ref.dtype)  # stored in the stream dtype
        dh = _dot_ut(dz, u_ref)
        dc = dc_new * f
        if has_mask:
            # frozen fraction of the cotangents bypasses the gates
            dh = dh + (1.0 - m) * dh_tot
            dc = dc + (1.0 - m) * dc_in
    dh_scr[:] = dh
    dc_scr[:] = dc

    @pl.when(t == T - 1)
    def _():
        dh0_ref[:] = dh
        dc0_ref[:] = dc


# ---------------------------------------------------------------------------
# Tiled kernels (U streamed in tiles; grid (T, K), chunk = 1)
# ---------------------------------------------------------------------------


def _lstm_tiled_kernel(*refs, hidden: int, htile: int, save_residuals: bool,
                       has_mask: bool):
    """Forward recurrence with U streamed in [htile, 4H] row-tiles.

    Grid (T, K), K = H/htile, k fastest. Per (t, k): accumulate
    ``z += h[:, k-tile] @ U[k-tile, :]`` into the full-width f32 z scratch;
    at the last tile, apply the gates and advance h/c. h is kept twice —
    tile-major ([K, B, htile] scratch, dynamically indexed by k for the
    matmul) and rebuilt with static slices after each step. With
    ``has_mask`` the previous full-width h is reassembled from the tiles
    for the carry blend."""
    n_in = 4 + has_mask
    xproj_ref, u_ref, h0_ref, c0_ref = refs[:4]
    mask_ref = refs[4] if has_mask else None
    ys_ref, hT_ref, cT_ref = refs[n_in:n_in + 3]
    rest = refs[n_in + 3:]
    if save_residuals:
        z_out_ref, cs_ref, h_tiles, c_scr, z_scr = rest
    else:
        h_tiles, c_scr, z_scr = rest
    t = pl.program_id(0)
    k = pl.program_id(1)
    T = pl.num_programs(0)
    K = pl.num_programs(1)
    H = hidden

    @pl.when((t == 0) & (k == 0))
    def _():
        for j in range(K):
            h_tiles[j] = h0_ref[:, j * htile : (j + 1) * htile]
        c_scr[:] = c0_ref[:]

    @pl.when(k == 0)
    def _():
        z_scr[:] = xproj_ref[0].astype(jnp.float32)

    z_scr[:] = z_scr[:] + jnp.dot(
        h_tiles[k].astype(u_ref.dtype), u_ref[:],
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == K - 1)
    def _():
        z = z_scr[:]
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H : 2 * H])
        g = jnp.tanh(z[:, 2 * H : 3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H :])
        c_new = f * c_scr[:] + i * g
        h_new = o * jnp.tanh(c_new)
        if has_mask:
            m = mask_ref[0][:, :1]
            h_prev = jnp.concatenate(
                [h_tiles[j] for j in range(K)], axis=1
            )  # previous step's full-width h
            c = m * c_new + (1.0 - m) * c_scr[:]
            h = m * h_new + (1.0 - m) * h_prev
        else:
            c = c_new
            h = h_new
        c_scr[:] = c
        ys_ref[0] = h
        if save_residuals:
            z_out_ref[0] = z.astype(z_out_ref.dtype)  # stream dtype
            cs_ref[0] = c
        for j in range(K):
            h_tiles[j] = h[:, j * htile : (j + 1) * htile]

        @pl.when(t == T - 1)
        def _():
            hT_ref[:] = h
            cT_ref[:] = c


def _lstm_bwd_tiled_kernel(*refs, hidden: int, ttile: int, has_mask: bool):
    """Tiled BPTT: computes ONLY the sequential part — dz_t and the dh/dc
    carries — streaming U in [H, ttile] column-tiles for the carry matmul.
    The weight cotangents (dU, dW, db) and dxs contract over all T·B outside
    the kernel as single large MXU matmuls (`_pallas_backward`). The cell
    state c_t is recomputed from (z_t, c_{t-1}). With ``has_mask`` the
    skipped cotangent ``(1-m)*dh_tot`` is staged in a scratch at the first
    tile and added to the carry at the last tile."""
    n_in = 6 + has_mask
    z_ref, dys_ref, cprev_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    u_ref, dhT_ref, dcT_ref = refs[3 + has_mask:n_in]
    dz_ref, dh0_ref, dc0_ref = refs[n_in:n_in + 3]
    scratch = refs[n_in + 3:]
    if has_mask:
        dh_scr, dc_scr, dhacc_scr, dz_tiles, dhskip_scr = scratch
    else:
        dh_scr, dc_scr, dhacc_scr, dz_tiles = scratch
    t = pl.program_id(0)
    k = pl.program_id(1)
    T = pl.num_programs(0)
    K = pl.num_programs(1)
    H = hidden

    @pl.when((t == 0) & (k == 0))
    def _():
        dh_scr[:] = dhT_ref[:]
        dc_scr[:] = dcT_ref[:]

    @pl.when(k == 0)
    def _():
        z = z_ref[0].astype(jnp.float32)
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H : 2 * H])
        g = jnp.tanh(z[:, 2 * H : 3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H :])
        c_prev = cprev_ref[0]
        tc = jnp.tanh(f * c_prev + i * g)  # tanh(c_new), recomputed
        dh_tot = dh_scr[:] + dys_ref[0]
        if has_mask:
            m = mask_ref[0][:, :1]
            dh_eff = m * dh_tot
            dc_eff = m * dc_scr[:]
            dhskip_scr[:] = (1.0 - m) * dh_tot
        else:
            dh_eff = dh_tot
            dc_eff = dc_scr[:]
        dc_new = dc_eff + dh_eff * o * (1.0 - tc * tc)
        do = dh_eff * tc * o * (1.0 - o)
        di = dc_new * g * i * (1.0 - i)
        df = dc_new * c_prev * f * (1.0 - f)
        dg = dc_new * i * (1.0 - g * g)
        dz = jnp.concatenate([di, df, dg, do], axis=1)  # [B, 4H] f32
        dz_ref[0] = dz.astype(dz_ref.dtype)  # stream dtype
        for j in range(K):
            dz_tiles[j] = dz[:, j * ttile : (j + 1) * ttile]
        if has_mask:
            dc_scr[:] = dc_new * f + (1.0 - m) * dc_scr[:]
        else:
            dc_scr[:] = dc_new * f
        dhacc_scr[:] = jnp.zeros_like(dhacc_scr)

    dhacc_scr[:] = dhacc_scr[:] + _dot_ut(dz_tiles[k], u_ref)

    @pl.when(k == K - 1)
    def _():
        if has_mask:
            dh_scr[:] = dhacc_scr[:] + dhskip_scr[:]
        else:
            dh_scr[:] = dhacc_scr[:]

        @pl.when(t == T - 1)
        def _():
            dh0_ref[:] = dh_scr[:]
            dc0_ref[:] = dc_scr[:]


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _pad_inputs_lane(xs, kernel, Dp: int, sdtype=jnp.float32):
    """Time-major xs (in the STREAM dtype ``sdtype`` — `_residual_dtype`)
    and W with the input width zero-padded to ``Dp`` (shared by the
    residentx forward AND backward, which must recompute z from
    bit-identical inputs — both call this with the same sdtype). Zero W
    rows multiply zero xs lanes: exact."""
    xs_t = jnp.moveaxis(xs, 0, 1).astype(sdtype)  # [T, B, D]
    D = xs_t.shape[-1]
    if Dp != D:
        xs_t = jnp.pad(xs_t, ((0, 0), (0, 0), (0, Dp - D)))
        kernel = jnp.pad(kernel, ((0, Dp - D), (0, 0)))
    return xs_t, kernel


def _pallas_forward(fused, xs, h0, c0, mask_tbl=None, *,
                    interpret: bool = False, save_residuals: bool = False,
                    allow_fusedx: bool = True):
    """xs [B,T,D] -> (ys [B,T,H], hT, cT[, z, cs]). fused: FusedLSTMParams.

    ``mask_tbl`` (optional) is the lane-broadcast f32 mask [T, B, LANE].
    ``save_residuals`` additionally returns residuals for the fused
    backward: the residentx strategy saves cs ONLY (z is recomputed in its
    backward; the z slot returns None), the others save z AND cs. Callers
    pairing a non-residentx backward must pass ``allow_fusedx=False`` so
    the z residual exists. Strategy comes from the shared cost model."""
    B, T, D = xs.shape
    H = fused.hidden_size
    dtype = fused.kernel.dtype
    pbytes = 2 if dtype == jnp.bfloat16 else 4
    has_mask = mask_tbl is not None
    Dp = (_pad_to_lane(D)
          if allow_fusedx and T >= _FUSEDX_MIN_T else None)
    plan = _plan_fwd(B, H, pbytes, save_residuals=save_residuals,
                     has_mask=has_mask, Dp=Dp)
    if plan is None:  # callers gate via supported(); belt-and-braces
        raise ValueError(f"no pallas forward plan for B={B}, H={H}")
    strategy, parg = plan
    htile = parg  # (tiled strategy; for resident[x] parg is the chunk cap)
    if strategy in ("residentx", "resident"):
        C = _chunk_for(T, parg)
    else:
        C = 1
    mask_spec = pl.BlockSpec((C, B, _LANE), lambda t, *k: (t, 0, 0),
                             memory_space=pltpu.VMEM)

    if strategy == "residentx":
        Dp = _pad_to_lane(D)
        xs_t, w = _pad_inputs_lane(xs, fused.kernel, Dp, _residual_dtype(dtype))
        in_specs = [
            pl.BlockSpec((C, B, Dp), lambda t, *k: (t, 0, 0),
                         memory_space=pltpu.VMEM),  # xs
            pl.BlockSpec(memory_space=pltpu.VMEM),  # W resident
            pl.BlockSpec(memory_space=pltpu.VMEM),  # bias
            pl.BlockSpec(memory_space=pltpu.VMEM),  # U resident
            pl.BlockSpec(memory_space=pltpu.VMEM),  # h0
            pl.BlockSpec(memory_space=pltpu.VMEM),  # c0
        ]
        operands = [xs_t, w, fused.bias.reshape(1, -1).astype(jnp.float32),
                    fused.recurrent, h0.astype(jnp.float32),
                    c0.astype(jnp.float32)]
        if has_mask:
            in_specs.append(mask_spec)
            operands.append(mask_tbl)
        out_specs = [
            pl.BlockSpec((C, B, H), lambda t, *k: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((T, B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ]
        if save_residuals:
            out_specs.append(
                pl.BlockSpec((C, B, H), lambda t, *k: (t, 0, 0),
                             memory_space=pltpu.VMEM)
            )
            out_shape.append(jax.ShapeDtypeStruct((T, B, H), jnp.float32))
        out = pl.pallas_call(
            functools.partial(
                _lstm_fwdx_kernel, hidden=H, dpad=Dp, chunk=C,
                save_c=save_residuals, has_mask=has_mask,
            ),
            grid=(T // C,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((B, H), jnp.float32),  # h
                pltpu.VMEM((B, H), jnp.float32),  # c
            ],
            interpret=interpret,
            name="lstm_rec_fwdx",
        )(*operands)
        ys = jnp.moveaxis(out[0], 0, 1)
        if save_residuals:
            return ys, out[1], out[2], None, out[3]
        return ys, out[1], out[2]

    # one big MXU matmul for every step's input projection, accumulated
    # f32 then STORED in the stream dtype (the r4 bandwidth analysis: the
    # [T,B,4H] xproj round-trip is a dominant HBM stream)
    sdtype = _residual_dtype(dtype)
    xproj = (
        jnp.einsum(
            "btd,dk->btk", xs.astype(dtype), fused.kernel,
            preferred_element_type=jnp.float32,
        )
        + fused.bias
    ).astype(sdtype)  # [B, T, 4H]
    xproj = jnp.moveaxis(xproj, 0, 1)  # [T, B, 4H]

    out_specs = [
        pl.BlockSpec((C, B, H), lambda t, *k: (t, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.VMEM),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((T, B, H), jnp.float32),
        jax.ShapeDtypeStruct((B, H), jnp.float32),
        jax.ShapeDtypeStruct((B, H), jnp.float32),
    ]
    if save_residuals:
        out_specs += [
            pl.BlockSpec((C, B, 4 * H), lambda t, *k: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, B, H), lambda t, *k: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((T, B, 4 * H), sdtype),  # z: stream dtype
            jax.ShapeDtypeStruct((T, B, H), jnp.float32),
        ]

    xproj_spec = pl.BlockSpec((C, B, 4 * H), lambda t, *k: (t, 0, 0),
                              memory_space=pltpu.VMEM)
    if strategy == "resident":
        kernel = functools.partial(
            _lstm_kernel, hidden=H, chunk=C, save_residuals=save_residuals,
            has_mask=has_mask,
        )
        grid = (T // C,)
        u_spec = pl.BlockSpec(memory_space=pltpu.VMEM)  # U resident
        scratch = [
            pltpu.VMEM((B, H), jnp.float32),  # h
            pltpu.VMEM((B, H), jnp.float32),  # c
        ]
    else:
        K = H // htile
        kernel = functools.partial(
            _lstm_tiled_kernel, hidden=H, htile=htile,
            save_residuals=save_residuals, has_mask=has_mask,
        )
        grid = (T, K)
        u_spec = pl.BlockSpec((htile, 4 * H), lambda t, k: (k, 0),
                              memory_space=pltpu.VMEM)  # U streamed
        scratch = [
            pltpu.VMEM((K, B, htile), jnp.float32),  # h, tile-major
            pltpu.VMEM((B, H), jnp.float32),  # c
            pltpu.VMEM((B, 4 * H), jnp.float32),  # z accumulator
        ]

    in_specs = [
        xproj_spec,
        u_spec,
        pl.BlockSpec(memory_space=pltpu.VMEM),  # h0
        pl.BlockSpec(memory_space=pltpu.VMEM),  # c0
    ]
    operands = [xproj, fused.recurrent,
                h0.astype(jnp.float32), c0.astype(jnp.float32)]
    if has_mask:
        in_specs.append(mask_spec)
        operands.append(mask_tbl)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name=("lstm_rec_fwd" if strategy == "resident"
              else "lstm_rec_fwd_tiled"),
    )(*operands)
    ys = jnp.moveaxis(out[0], 0, 1)
    if save_residuals:
        return ys, out[1], out[2], out[3], out[4]
    return ys, out[1], out[2]


def _pallas_backward(fused, params, xs, h0, c0, mask_tbl, ys, z, cs,
                     dys, dhT, dcT, *, interpret: bool = False):
    """Fused BPTT via `_lstm_bwd_kernel` / `_lstm_bwd_tiled_kernel` + big
    MXU matmuls outside.

    Returns per-gate grads in the LSTMParams structure plus (dxs, dh0, dc0).
    """
    B, T, D = xs.shape
    H = fused.hidden_size
    dtype = fused.kernel.dtype
    pbytes = 2 if dtype == jnp.bfloat16 else 4
    sdtype = _residual_dtype(dtype)  # dtype of the z/dz/xs HBM streams
    has_mask = mask_tbl is not None
    # z is None ⇔ the forward ran residentx and saved cs only — the
    # recompute-z backward is then the ONLY strategy whose residual
    # contract matches (the planner guarantees it fits in that case)
    Dp = _pad_to_lane(D) if z is None else None
    plan = _plan_bwd(B, H, pbytes, has_mask, Dp)
    if plan is None or (z is None and plan[0] != "residentx"):
        raise ValueError(f"no pallas backward plan for B={B}, H={H}")
    strategy, parg = plan
    ttile = parg  # (tiled strategy; for residentx parg is the chunk cap)

    ys_t = jnp.moveaxis(ys, 0, 1)  # [T, B, H] f32
    h_prev = jnp.concatenate([h0.astype(jnp.float32)[None], ys_t[:-1]], axis=0)
    c_prev = jnp.concatenate([c0.astype(jnp.float32)[None], cs[:-1]], axis=0)
    dys_t = jnp.moveaxis(dys.astype(jnp.float32), 0, 1)
    # U goes in as stored, [H, 4H]; the kernels contract its second axis
    # (`_dot_ut`). A `.T` out here made XLA carry all 96 f32[1024,1024] of
    # config 5's state transposed: 240 copies, 1.92 ms a step (PERF.md PR 33).
    u = fused.recurrent

    if strategy == "residentx":
        C = _chunk_for(T, parg)
        n = T // C
        rev = lambda t: (n - 1 - t, 0, 0)  # reverse-time grid
        xs_t, w = _pad_inputs_lane(xs, fused.kernel, Dp, sdtype)
        in_specs = [
            pl.BlockSpec((C, B, Dp), rev, memory_space=pltpu.VMEM),  # xs
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),   # dys
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),   # c_prev
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),   # h_prev
        ]
        operands = [xs_t, dys_t, c_prev, h_prev]
        if has_mask:
            in_specs.append(
                pl.BlockSpec((C, B, _LANE), rev, memory_space=pltpu.VMEM)
            )
            operands.append(mask_tbl)
        in_specs += [
            pl.BlockSpec(memory_space=pltpu.VMEM),                   # W
            pl.BlockSpec(memory_space=pltpu.VMEM),                   # bias
            pl.BlockSpec(memory_space=pltpu.VMEM),                   # U
            pl.BlockSpec(memory_space=pltpu.VMEM),                   # dhT
            pl.BlockSpec(memory_space=pltpu.VMEM),                   # dcT
        ]
        operands += [w, fused.bias.reshape(1, -1).astype(jnp.float32), u,
                     dhT.astype(jnp.float32), dcT.astype(jnp.float32)]
        dz, dh0, dc0 = pl.pallas_call(
            functools.partial(_lstm_bwdx_kernel, hidden=H, dpad=Dp,
                              chunk=C, has_mask=has_mask),
            grid=(n,),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((C, B, 4 * H), rev, memory_space=pltpu.VMEM),  # dz
                pl.BlockSpec(memory_space=pltpu.VMEM),                   # dh0
                pl.BlockSpec(memory_space=pltpu.VMEM),                   # dc0
            ],
            out_shape=[
                jax.ShapeDtypeStruct((T, B, 4 * H), sdtype),  # dz stream
                jax.ShapeDtypeStruct((B, H), jnp.float32),
                jax.ShapeDtypeStruct((B, H), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((B, H), jnp.float32),
                pltpu.VMEM((B, H), jnp.float32),
            ],
            interpret=interpret,
            name="lstm_rec_bwdx",
        )(*operands)
    elif strategy == "resident":
        C = _chunk_for(T, parg)
        n = T // C
        rev = lambda t: (n - 1 - t, 0, 0)  # reverse-time grid
        kernel = functools.partial(_lstm_bwd_kernel, hidden=H, chunk=C,
                                   has_mask=has_mask)
        in_specs = [
            pl.BlockSpec((C, B, 4 * H), rev, memory_space=pltpu.VMEM),  # z
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),   # dys
            pl.BlockSpec((C, B, H), rev, memory_space=pltpu.VMEM),   # c_prev
        ]
        operands = [z, dys_t, c_prev]
        if has_mask:
            in_specs.append(
                pl.BlockSpec((C, B, _LANE), rev, memory_space=pltpu.VMEM)
            )
            operands.append(mask_tbl)
        in_specs += [
            pl.BlockSpec(memory_space=pltpu.VMEM),                   # U
            pl.BlockSpec(memory_space=pltpu.VMEM),                   # dhT
            pl.BlockSpec(memory_space=pltpu.VMEM),                   # dcT
        ]
        operands += [u, dhT.astype(jnp.float32), dcT.astype(jnp.float32)]
        dz, dh0, dc0 = pl.pallas_call(
            kernel,
            grid=(n,),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((C, B, 4 * H), rev, memory_space=pltpu.VMEM),  # dz
                pl.BlockSpec(memory_space=pltpu.VMEM),                   # dh0
                pl.BlockSpec(memory_space=pltpu.VMEM),                   # dc0
            ],
            out_shape=[
                jax.ShapeDtypeStruct((T, B, 4 * H), sdtype),  # dz stream
                jax.ShapeDtypeStruct((B, H), jnp.float32),
                jax.ShapeDtypeStruct((B, H), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((B, H), jnp.float32),
                pltpu.VMEM((B, H), jnp.float32),
            ],
            interpret=interpret,
            name="lstm_rec_bwd",
        )(*operands)
    else:
        K = 4 * H // ttile
        rev1 = lambda t, k: (T - 1 - t, 0, 0)
        kernel = functools.partial(_lstm_bwd_tiled_kernel, hidden=H,
                                   ttile=ttile, has_mask=has_mask)
        in_specs = [
            pl.BlockSpec((1, B, 4 * H), rev1, memory_space=pltpu.VMEM),  # z
            pl.BlockSpec((1, B, H), rev1, memory_space=pltpu.VMEM),  # dys
            pl.BlockSpec((1, B, H), rev1, memory_space=pltpu.VMEM),  # c_prev
        ]
        operands = [z, dys_t, c_prev]
        if has_mask:
            in_specs.append(
                pl.BlockSpec((1, B, _LANE), rev1, memory_space=pltpu.VMEM)
            )
            operands.append(mask_tbl)
        in_specs += [
            pl.BlockSpec((H, ttile), lambda t, k: (0, k),
                         memory_space=pltpu.VMEM),                   # U tile
            pl.BlockSpec(memory_space=pltpu.VMEM),                   # dhT
            pl.BlockSpec(memory_space=pltpu.VMEM),                   # dcT
        ]
        operands += [u, dhT.astype(jnp.float32), dcT.astype(jnp.float32)]
        scratch = [
            pltpu.VMEM((B, H), jnp.float32),          # dh carry
            pltpu.VMEM((B, H), jnp.float32),          # dc carry
            pltpu.VMEM((B, H), jnp.float32),          # dh accumulator
            pltpu.VMEM((K, B, ttile), jnp.float32),   # dz, tile-major
        ]
        if has_mask:
            scratch.append(pltpu.VMEM((B, H), jnp.float32))  # dh skip
        dz, dh0, dc0 = pl.pallas_call(
            kernel,
            grid=(T, K),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, B, 4 * H), rev1, memory_space=pltpu.VMEM),  # dz
                pl.BlockSpec(memory_space=pltpu.VMEM),                   # dh0
                pl.BlockSpec(memory_space=pltpu.VMEM),                   # dc0
            ],
            out_shape=[
                jax.ShapeDtypeStruct((T, B, 4 * H), sdtype),  # dz stream
                jax.ShapeDtypeStruct((B, H), jnp.float32),
                jax.ShapeDtypeStruct((B, H), jnp.float32),
            ],
            scratch_shapes=scratch,
            interpret=interpret,
            name="lstm_rec_bwd_tiled",
        )(*operands)

    # dU contracts over all T·B at once — one large MXU matmul for EVERY
    # strategy (the sequential kernels emit dz anyway; accumulating dU
    # in-kernel would serialize an extra MXU op with the reverse chain).
    dU = jnp.einsum(
        "tbh,tbk->hk", h_prev.astype(dtype), dz.astype(dtype),
        preferred_element_type=jnp.float32,
    )

    # input-projection cotangents: one MXU matmul each (XLA's job)
    xs_t = jnp.moveaxis(xs, 0, 1).astype(dtype)  # [T, B, D]
    dz_c = dz.astype(dtype)
    dW = jnp.einsum(
        "tbd,tbk->dk", xs_t, dz_c, preferred_element_type=jnp.float32
    )
    db = jnp.sum(dz, axis=(0, 1), dtype=jnp.float32)
    dxs = jnp.moveaxis(
        jnp.einsum(
            "tbk,dk->tbd", dz_c, fused.kernel,
            preferred_element_type=jnp.float32,
        ),
        0, 1,
    ).astype(xs.dtype)

    Ws = jnp.split(dW, 4, axis=1)
    Us = jnp.split(dU, 4, axis=1)
    bs = jnp.split(db, 4)
    dparams = LSTMParams(*Ws, *Us, *bs)
    dparams = jax.tree.map(lambda g, p: g.astype(p.dtype), dparams, params)
    return dparams, dxs, dh0.astype(h0.dtype), dc0.astype(c0.dtype)


# ---------------------------------------------------------------------------
# custom-VJP core + public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _scan_core(params, xs, h0, c0, mask_tbl, compute_dtype, interpret,
               remat_chunk, unroll, has_mask):
    fused = fuse_params(params, compute_dtype=compute_dtype)
    ys, hT, cT = _pallas_forward(
        fused, xs, h0, c0, mask_tbl if has_mask else None, interpret=interpret
    )
    return ys, hT, cT


def _mask_bt(mask_tbl):
    """Recover the [B, T] bool mask from the lane-broadcast [T, B, LANE]."""
    return jnp.moveaxis(mask_tbl[:, :, 0] > 0, 0, 1)


def _reference(params, xs, h0, c0, mask, compute_dtype, remat_chunk, unroll):
    (hT, cT), ys = lstm_scan(
        params, xs, (h0, c0), mask=mask,
        compute_dtype=compute_dtype, remat_chunk=remat_chunk, unroll=unroll,
    )
    return ys, hT, cT


def _scan_core_fwd(params, xs, h0, c0, mask_tbl, compute_dtype, interpret,
                   remat_chunk, unroll, has_mask):
    fused = fuse_params(params, compute_dtype=compute_dtype)
    B, T, D = xs.shape
    H = fused.hidden_size
    pbytes = 2 if fused.kernel.dtype == jnp.bfloat16 else 4
    Dp = _pad_to_lane(D) if T >= _FUSEDX_MIN_T else None
    # gate rationale lives on chosen_bwd_strategy
    strategy = chosen_bwd_strategy(B, T, H, pbytes, has_mask=has_mask, Dp=Dp,
                                   remat_chunk=remat_chunk)
    fusedx = strategy == "residentx"
    use_fused_bwd = strategy != "recompute"
    if use_fused_bwd:
        ys, hT, cT, z, cs = _pallas_forward(
            fused, xs, h0, c0, mask_tbl if has_mask else None,
            interpret=interpret, save_residuals=True, allow_fusedx=fusedx,
        )
        return (ys, hT, cT), (params, xs, h0, c0, mask_tbl, ys, z, cs)
    out = _scan_core(
        params, xs, h0, c0, mask_tbl, compute_dtype, interpret, remat_chunk,
        unroll, has_mask,
    )
    return out, (params, xs, h0, c0, mask_tbl, None, None, None)


def _scan_core_bwd(compute_dtype, interpret, remat_chunk, unroll, has_mask,
                   residuals, cotangents):
    params, xs, h0, c0, mask_tbl, ys, z, cs = residuals
    if cs is not None:
        # Fused Pallas BPTT; z is None ⇔ the residentx pair (recompute-z).
        fused = fuse_params(params, compute_dtype=compute_dtype)
        dys, dhT, dcT = cotangents
        dparams, dxs, dh0, dc0 = _pallas_backward(
            fused, params, xs, h0, c0, mask_tbl if has_mask else None,
            ys, z, cs, dys, dhT, dcT, interpret=interpret,
        )
        return dparams, dxs, dh0, dc0, jnp.zeros_like(mask_tbl)
    # Remat-style backward: recompute the forward with the pure-jax scan and
    # pull gradients through it — bit-exact with the reference BPTT.
    # remat_chunk bounds the recompute's own residual memory to O(T/chunk)
    # carries, so --use-pallas composes with --remat-chunk on long sequences.
    mask = _mask_bt(mask_tbl) if has_mask else None
    _, vjp = jax.vjp(
        lambda p, x, h, c: _reference(
            p, x, h, c, mask, compute_dtype, remat_chunk, unroll
        ),
        params, xs, h0, c0,
    )
    dparams, dxs, dh0, dc0 = vjp(cotangents)
    return dparams, dxs, dh0, dc0, jnp.zeros_like(mask_tbl)


_scan_core.defvjp(_scan_core_fwd, _scan_core_bwd)


def _pad_params_lane(params: LSTMParams, hp: int) -> LSTMParams:
    """Zero-pad every gate block from H to hp (lane alignment). Exactly
    gradient-neutral — see the module docstring's padding analysis."""
    pad = hp - params.hidden_size
    pw = lambda a: jnp.pad(a, ((0, 0), (0, pad)))
    pu = lambda a: jnp.pad(a, ((0, pad), (0, pad)))
    pb = lambda a: jnp.pad(a, (0, pad))
    return LSTMParams(
        pw(params.W_i), pw(params.W_f), pw(params.W_g), pw(params.W_o),
        pu(params.U_i), pu(params.U_f), pu(params.U_g), pu(params.U_o),
        pb(params.b_i), pb(params.b_f), pb(params.b_g), pb(params.b_o),
    )


def pallas_lstm_scan(
    params: LSTMParams,
    xs: jax.Array,
    carry: tuple[jax.Array, jax.Array] | None = None,
    *,
    mask: jax.Array | None = None,
    reverse: bool = False,
    compute_dtype=None,
    remat_chunk: int | None = None,
    unroll: int = 1,
    interpret: bool = False,
):
    """Drop-in fused-kernel variant of `lstm_scan` (mask + reverse included).

    ``mask`` ([B, T] bool) freezes the carry at False steps; ``reverse``
    scans right-to-left. Reverse is realised by flipping the time axis
    outside the custom VJP (the kernels always run forward), so a reversed
    masked scan over a right-padded batch — the bi-LSTM's backward direction
    — walks the padding first with a frozen carry, exactly like `lstm_scan`.

    Backward strategy (module docstring): fused BPTT kernel by default;
    setting ``remat_chunk`` selects the recompute backward (bounded residual
    memory), where ``remat_chunk``/``unroll`` apply to its recompute scan
    exactly as in `lstm_scan`. Returns ``((hT, cT), ys)``.

    Hidden sizes off the 128-lane grid (e.g. 650) are padded internally;
    the pad/slice sits outside the custom VJP, so gradients transpose
    through it automatically and exactly.
    """
    B, T, _ = xs.shape
    H = params.hidden_size
    hp = _pad_to_lane(H)
    if reverse:
        xs = jnp.flip(xs, axis=1)
        if mask is not None:
            mask = jnp.flip(mask, axis=1)
    if carry is None:
        h0 = jnp.zeros((B, hp), jnp.float32)
        c0 = jnp.zeros((B, hp), jnp.float32)
    else:
        h0, c0 = carry
        if hp != H:
            h0 = jnp.pad(h0, ((0, 0), (0, hp - H)))
            c0 = jnp.pad(c0, ((0, 0), (0, hp - H)))
    run_params = _pad_params_lane(params, hp) if hp != H else params
    has_mask = mask is not None
    if has_mask:
        mask_tbl = jnp.broadcast_to(
            jnp.moveaxis(mask, 0, 1).astype(jnp.float32)[:, :, None],
            (T, B, _LANE),
        )
    else:
        mask_tbl = jnp.zeros((1, 1, _LANE), jnp.float32)  # unused dummy
    ys, hT, cT = _scan_core(run_params, xs, h0, c0, mask_tbl, compute_dtype,
                            interpret, remat_chunk, unroll, has_mask)
    if hp != H:
        ys, hT, cT = ys[..., :H], hT[:, :H], cT[:, :H]
    if reverse:
        ys = jnp.flip(ys, axis=1)
    return (hT, cT), ys
