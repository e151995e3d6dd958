"""Parallel-scan BPTT for the LSTM recurrence (BPPSA-style).

The training bottleneck for long sequences is not the matmuls — it is the
T-deep sequential dependency chain that `lax.scan` (ops/scan.py) and its
reverse-mode transpose walk step by step (at T=400 the chain latency,
not FLOPs, is the binding constraint). *BPPSA: Scaling Back-propagation
by Parallel Scan Algorithm* (PAPERS.md) observes that even though the
forward cell is nonlinear, **backprop through a recurrence is a linear
chain of per-step Jacobian operators**:

    lambda_{t-1} = A_t^T (lambda_t + e_t)

with ``lambda_t`` the adjoint of the carry ``(h_t, c_t)``, ``e_t`` the
cotangent injected by the step's output ``y_t = h_t``, and ``A_t`` the
per-step carry Jacobian. Affine operators compose associatively, so the
whole backward pass is an associative scan — O(log T) depth instead of
O(T) — of MXU-friendly composes.

Three-phase tiled backward (the chunking of `remat_chunk` /
`parallel/sequence_parallel.py` is the natural tile for the scan tree):

1. **Tile build** (depth = tile): within each of the T/tile chunks —
   all chunks advancing together in ONE `lax.scan` of length ``tile`` —
   compose the per-step operators into one dense affine chunk operator
   ``(M_c, d_c)``. The per-step operator is *never* materialized as a
   dense [2H, 2H] block: it is applied in factored form — gate-local
   diagonal terms (``sigma'``/``tanh'`` products) plus ONE shared
   ``[*, 4H] @ [4H, H]`` matmul against the fused recurrent kernel — to
   the 2H+1 columns of the accumulating chunk operator at once.
2. **Tree compose** (depth = log2(T/tile)): `jax.lax.associative_scan`
   over the chunk operators (dense ``[B, 2H, 2H]`` batched matmuls —
   the only place dense blocks exist, which is what the `plan_bytes`
   memory model below prices) yields the adjoint at every chunk
   boundary.
3. **Interior replay** (depth = tile): all chunks again advance in one
   scan from their boundary adjoints, emitting the per-step gate
   cotangents ``gz_t``; parameter and input gradients then come from
   three large batched matmuls over the whole [T, B, 4H] block.

Residual policy mirrors `remat_chunk`'s recompute trade: the forward
stores only the ``h``/``c`` sequences (2 x [T, B, H]); the backward
rebuilds every gate in ONE fused [T*B, 4H] matmul instead of storing
per-step activations.

The FLOP trade is real and priced honestly: the dense tile/tree
composes do O(H) more arithmetic than sequential BPTT's vector chain.
On a latency-bound accelerator chain (small per-step matmuls, T deep)
the log-depth tree wins; on a throughput-bound CPU it usually does not
— `tests_tpu/test_parallel_scan_tpu.py` is the hardware >= 1.0x gate.

``resolve_bptt`` implements the ``bptt="auto"`` policy (ops/scan.py):
assoc only when the `plan_bytes` memory model fits the budget AND
T >= `AUTO_MIN_T`; every auto resolution that falls back to sequential
bumps a trace-time counter surfaced in the run's ``metrics_snapshot``
record (train/loop.py) so supervised restarts can detect a mode flip
between resume legs.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from .lstm_cell import LSTMParams, fuse_params, lstm_step_hoisted, zero_carry

#: minimum T for ``bptt="auto"`` to pick the assoc path: below this the
#: sequential chain is short enough that the tree's extra FLOPs and the
#: dense-block traffic cannot pay for the saved depth.
AUTO_MIN_T = 128

#: default budget for the dense chunk-operator working set (HBM-level —
#: the training twin of ops/pallas_decode's VMEM plan, at the memory
#: tier this path actually pressures). Override: LSTM_TSP_ASSOC_BUDGET_MB.
_DEFAULT_BUDGET_MB = 1024

#: trace-time counters (bumped when a scan RESOLVES, i.e. once per XLA
#: trace, not per step): ``assoc_traces`` = scans that took the assoc
#: path; ``sequential_fallbacks`` = ``auto`` requests the memory plan or
#: T-threshold pushed back to sequential. train/loop.py mirrors the
#: fallback delta into obs and cli.py stamps both into metrics_snapshot.
_STATS = {"assoc_traces": 0, "sequential_fallbacks": 0}

BPTT_MODES = ("sequential", "assoc", "auto")


def assoc_stats() -> dict:
    """Snapshot of the trace-time resolution counters (copies)."""
    return dict(_STATS)


def reset_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


def _budget_bytes() -> int:
    return int(os.environ.get(
        "LSTM_TSP_ASSOC_BUDGET_MB", _DEFAULT_BUDGET_MB)) * 2**20


def pick_tile(T: int, remat_chunk: int | None = None, *,
              target: int = 16) -> int:
    """Tile (chunk) length for the scan tree: `remat_chunk` when it
    divides T (the forward's chunking IS the tree's natural tile),
    else the divisor of T closest to ``target``."""
    if remat_chunk and T % remat_chunk == 0:
        return remat_chunk
    divisors = [d for d in range(1, T + 1) if T % d == 0]
    return min(divisors, key=lambda d: (abs(d - target), d))


def plan_bytes(batch: int, T: int, hidden: int, *,
               tile: int | None = None) -> int:
    """Working-set bytes of the assoc backward (f32 throughout).

    Dominant term: the dense chunk operators — [T/tile, B, 2H, 2H+1]
    augmented blocks, counted x3 for the associative-scan combine tree's
    intermediate copies. Plus the tile-build scan's double-buffered
    carry, the gate recompute / cotangent block ([T, B, 4H] x2), the
    factor tensors, and the h/c residuals. Mirrors the
    `ops/pallas_decode.plan_bytes` cost-model style: count every live
    operand once, prefer over-counting to an OOM surprise.
    """
    tile = tile or pick_tile(T)
    n_chunks = max(T // tile, 1)
    H = hidden
    K = 2 * H + 1
    v = 3 * n_chunks * batch * 2 * H * K * 4      # chunk ops through the tree
    v += 2 * n_chunks * batch * 2 * H * K * 4     # build-scan carry (dbl buf)
    v += 2 * T * batch * 4 * H * 4                # gate recompute + gz block
    v += 6 * T * batch * H * 4                    # per-step factor tensors
    v += 3 * T * batch * H * 4                    # h/c residuals + ys cotangent
    return v


def plan_fits(batch: int, T: int, hidden: int, *,
              tile: int | None = None) -> bool:
    return plan_bytes(batch, T, hidden, tile=tile) <= _budget_bytes()


def resolve_bptt(mode: str, batch: int, T: int, hidden: int, *,
                 remat_chunk: int | None = None) -> str:
    """Resolve a ``bptt=`` knob value to a concrete path at trace time.

    ``sequential``/``assoc`` are honored as written (explicit ``assoc``
    trusts the caller — parity tests need a deterministic path);
    ``auto`` takes assoc only when T >= `AUTO_MIN_T` AND `plan_fits`,
    else falls back to sequential and counts the fallback.
    """
    if mode not in BPTT_MODES:
        raise ValueError(
            f"bptt={mode!r} not in {BPTT_MODES} — pick 'sequential' "
            "(reverse-mode through the scan), 'assoc' (parallel-scan "
            "adjoint chain), or 'auto' (assoc when the memory plan fits "
            f"and T >= {AUTO_MIN_T})")
    if mode == "auto":
        tile = pick_tile(T, remat_chunk)
        if T >= AUTO_MIN_T and plan_fits(batch, T, hidden, tile=tile):
            return "assoc"
        _STATS["sequential_fallbacks"] += 1
        return "sequential"
    return mode


# ---- the custom-VJP core (forward time order; wrapper handles reverse) ----


def _project(fused, xs_t):
    """Input projection for the whole [T, B, D] block in one MXU matmul —
    same hoisting as ops/scan.py `lstm_scan.project` (float32 out)."""
    z = jnp.dot(xs_t.astype(fused.kernel.dtype), fused.kernel,
                preferred_element_type=jnp.float32)
    return z + fused.bias


def _apply_adjoint(U_T, coeff, gh, gc):
    """Apply one step's adjoint operator ``A_t^T`` (factored form — the
    gate-local diagonals plus one shared matmul against the fused
    recurrent kernel; dense [2H, 2H] blocks never appear here) to a
    stack of K adjoint vectors.

    ``coeff`` = (q, ci, cf, cg, co, f, m) each [..., H] (m [..., 1] or
    None); ``gh``/``gc`` [..., K, H]. Returns (gh_prev, gc_prev, gz)
    with ``gz`` [..., K, 4H] the pre-activation cotangents (gate order
    i, f, g, o — `ops/lstm_cell.GATE_ORDER`).
    """
    q, ci, cf, cg, co, f, m = coeff
    col = lambda a: a[..., None, :]  # noqa: E731 — broadcast over K
    if m is not None:
        mm = col(m)
        gh_m = gh * mm
        gc_m = gc * mm
    else:
        gh_m, gc_m = gh, gc
    gc_hat = gc_m + gh_m * col(q)
    gz = jnp.concatenate([
        gc_hat * col(ci),
        gc_hat * col(cf),
        gc_hat * col(cg),
        gh_m * col(co),
    ], axis=-1)
    gh_prev = jnp.dot(gz, U_T)
    gc_prev = gc_hat * col(f)
    if m is not None:
        inv = 1.0 - mm
        gh_prev = gh_prev + inv * gh
        gc_prev = gc_prev + inv * gc
    return gh_prev, gc_prev, gz


def _forward_scan(fused, xs_t, carry, mask_t):
    """The sequential forward (identical step math to ops/scan.py),
    additionally emitting the c sequence next to ys — the only
    residuals the assoc backward needs (gates rebuild in one matmul)."""

    def step(c, inp):
        if mask_t is None:
            new_carry, _ = lstm_step_hoisted(fused, c, inp)
        else:
            zx, mb = inp
            (h_new, c_new), _ = lstm_step_hoisted(fused, c, zx)
            h = jnp.where(mb, h_new, c[0])
            cc = jnp.where(mb, c_new, c[1])
            new_carry = (h, cc)
        return new_carry, new_carry

    inp = _project(fused, xs_t)
    if mask_t is not None:
        inp = (inp, mask_t)
    (hT, cT), (hs, cs) = lax.scan(step, carry, inp)
    return (hT, cT), hs, cs


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _assoc_core(tile, compute_dtype, params, xs, carry, mask_f):
    out, _ = _assoc_core_fwd(tile, compute_dtype, params, xs, carry, mask_f)
    return out


def _assoc_core_fwd(tile, compute_dtype, params, xs, carry, mask_f):
    fused = fuse_params(params, compute_dtype=compute_dtype)
    xs_t = jnp.moveaxis(xs, 0, 1)  # [T, B, D]
    mask_t = None
    if mask_f is not None:
        mask_t = jnp.moveaxis(mask_f, 0, 1)[..., None] != 0
    (hT, cT), hs, cs = _forward_scan(fused, xs_t, carry, mask_t)
    out = ((hT, cT), jnp.moveaxis(hs, 0, 1))
    return out, (params, xs, carry, mask_f, hs, cs)


def _assoc_core_bwd(tile, compute_dtype, res, ct):
    params, xs, carry, mask_f, hs, cs = res
    (ghT, gcT), gys_bm = ct
    fused = fuse_params(params, compute_dtype=compute_dtype)
    B, T, _ = xs.shape
    H = params.hidden_size
    n_chunks = T // tile
    K = 2 * H + 1
    f32 = jnp.float32

    xs_t = jnp.moveaxis(xs, 0, 1)
    gys = jnp.moveaxis(gys_bm, 0, 1).astype(f32)          # [T, B, H]
    h0, c0 = carry
    h_prev = jnp.concatenate([h0[None].astype(hs.dtype), hs[:-1]], axis=0)
    c_prev = jnp.concatenate([c0[None].astype(cs.dtype), cs[:-1]], axis=0)

    # gate recompute: ONE fused matmul over all T steps (the remat-style
    # trade — h/c residuals in, every sigma/tanh activation back out)
    z = _project(fused, xs_t) + jnp.dot(
        h_prev.astype(fused.recurrent.dtype), fused.recurrent,
        preferred_element_type=f32)
    zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
    gi = jax.nn.sigmoid(zi)
    gf = jax.nn.sigmoid(zf)
    gg = jnp.tanh(zg)
    go = jax.nn.sigmoid(zo)
    # tanh of the UNFROZEN cell update (== cs at unmasked steps; at
    # masked steps the factors are m-zeroed, but rebuilding from the
    # gates keeps them exact regardless)
    tc = jnp.tanh(gf * c_prev + gi * gg)

    # gate-local diagonal factors of A_t^T
    q = go * (1.0 - tc * tc)
    ci = gg * gi * (1.0 - gi)
    cf = c_prev * gf * (1.0 - gf)
    cg = gi * (1.0 - gg * gg)
    co = tc * go * (1.0 - go)
    m = None
    if mask_f is not None:
        m = jnp.moveaxis(mask_f, 0, 1).astype(f32)[..., None]  # [T, B, 1]
    U_T = fused.recurrent.astype(f32).T                        # [4H, H]

    def chunkify(a):  # [T, ...] -> [tile, NC, ...] (local time leading)
        return a.reshape(n_chunks, tile, *a.shape[1:]).swapaxes(0, 1)

    coeffs = tuple(chunkify(a) for a in (q, ci, cf, cg, co, gf))
    coeffs = coeffs + ((chunkify(m) if m is not None else None),)
    gys_ch = chunkify(gys)                                     # [tile, NC, B, H]

    # ---- phase 1: build each chunk's affine operator (all chunks in
    # one scan; per-step op applied in factored form to the K columns) --
    eyeh = jnp.eye(H, dtype=f32)
    zrow = jnp.zeros((1, H), f32)
    Mgh0 = jnp.concatenate([eyeh, jnp.zeros((H, H), f32), zrow], axis=0)
    Mgc0 = jnp.concatenate([jnp.zeros((H, H), f32), eyeh, zrow], axis=0)
    Mgh0 = jnp.broadcast_to(Mgh0, (n_chunks, B, K, H))
    Mgc0 = jnp.broadcast_to(Mgc0, (n_chunks, B, K, H))

    def build_step(acc, inp):
        Mgh, Mgc = acc
        coeff, gy = inp
        # fold this step's output cotangent into the affine column
        Mgh = Mgh.at[..., K - 1, :].add(gy)
        gh2, gc2, _ = _apply_adjoint(U_T, coeff, Mgh, Mgc)
        return (gh2, gc2), None

    (Mgh, Mgc), _ = lax.scan(build_step, (Mgh0, Mgc0), (coeffs, gys_ch),
                             reverse=True)

    # ---- phase 2: log-depth tree over the chunk operators -------------
    # row convention: lambda_prev = lambda_next @ M + d
    M_blocks = jnp.concatenate([Mgh[:, :, :2 * H, :], Mgc[:, :, :2 * H, :]],
                               axis=-1)                    # [NC, B, 2H, 2H]
    d_vecs = jnp.concatenate([Mgh[:, :, K - 1, :], Mgc[:, :, K - 1, :]],
                             axis=-1)                      # [NC, B, 2H]

    def combine(a, b):
        # suffix composition in row convention (lambda' = lambda @ M + d):
        # under associative_scan(reverse=True) the FIRST argument holds
        # the later-in-time (applied-first) side, so the composed map is
        # lambda @ M_a @ M_b + d_a @ M_b + d_b (validated against a
        # step-at-a-time reference in tests/test_parallel_scan.py)
        Ma, da = a
        Mb, db = b
        return (jnp.matmul(Ma, Mb),
                jnp.einsum("cbi,cbio->cbo", da, Mb) + db)

    S_M, S_d = lax.associative_scan(combine, (M_blocks, d_vecs),
                                    reverse=True, axis=0)
    lam_fin = jnp.concatenate([ghT.astype(f32), gcT.astype(f32)], axis=-1)
    applied = jnp.einsum("bi,cbio->cbo", lam_fin, S_M) + S_d   # [NC, B, 2H]
    # adjoint entering chunk c from the right = suffix over chunks > c
    lam_end = jnp.concatenate([applied[1:], lam_fin[None]], axis=0)

    # ---- phase 3: interior replay (all chunks in one scan), emitting
    # the per-step gate cotangents -------------------------------------
    def replay_step(acc, inp):
        gh, gc = acc
        coeff, gy = inp
        gh = gh + gy
        gh2, gc2, gz = _apply_adjoint(
            U_T, coeff, gh[..., None, :], gc[..., None, :])
        return (gh2[..., 0, :], gc2[..., 0, :]), gz[..., 0, :]

    (gh_in, gc_in), gz_ch = lax.scan(
        replay_step, (lam_end[..., :H], lam_end[..., H:]),
        (coeffs, gys_ch), reverse=True)
    gz = gz_ch.swapaxes(0, 1).reshape(T, B, 4 * H)             # [T, B, 4H]

    # ---- gradients: three large batched matmuls ----------------------
    dt = fused.kernel.dtype
    g_kernel = jnp.einsum("tbd,tbk->dk", xs_t.astype(dt), gz).astype(f32)
    g_recur = jnp.einsum("tbh,tbk->hk", h_prev.astype(dt), gz).astype(f32)
    g_bias = gz.sum(axis=(0, 1))
    g_xs = jnp.einsum("tbk,dk->tbd", gz, fused.kernel.astype(f32))
    g_xs = jnp.moveaxis(g_xs, 0, 1).astype(xs.dtype)
    gW = jnp.split(g_kernel, 4, axis=1)
    gU = jnp.split(g_recur, 4, axis=1)
    gb = jnp.split(g_bias, 4)
    g_params = LSTMParams(*gW, *gU, *gb)
    g_params = jax.tree.map(lambda g, p: g.astype(p.dtype), g_params, params)
    g_carry = (gh_in[0].astype(h0.dtype), gc_in[0].astype(c0.dtype))
    g_mask = None if mask_f is None else jnp.zeros_like(mask_f)
    return g_params, g_xs, g_carry, g_mask


_assoc_core.defvjp(_assoc_core_fwd, _assoc_core_bwd)


def assoc_lstm_scan(
    params: LSTMParams,
    xs: jax.Array,
    carry: tuple[jax.Array, jax.Array] | None = None,
    *,
    mask: jax.Array | None = None,
    reverse: bool = False,
    remat_chunk: int | None = None,
    compute_dtype=None,
    unroll: int = 1,
    tile: int | None = None,
):
    """`ops/scan.lstm_scan` with the associative-scan backward.

    Same signature and return contract — ``((h_T, c_T), ys)``, ys
    [B, T, H] — and the same forward values (the forward is the same
    hoisted-projection scan); only the VJP differs. ``unroll`` is
    accepted for signature parity and ignored (the backward's depth
    comes from the tile/tree split, not loop unrolling). ``tile``
    defaults to `pick_tile` (remat_chunk when it divides T).
    """
    B, T, _ = xs.shape
    if remat_chunk is not None and T % remat_chunk != 0:
        raise ValueError(
            f"T={T} not divisible by remat_chunk={remat_chunk} — a tail "
            "chunk would silently change remat (and bptt-mode) semantics; "
            "pad or pick a divisor")
    del unroll
    if carry is None:
        carry = zero_carry(B, params.hidden_size)
    if tile is None:
        tile = pick_tile(T, remat_chunk)
    if T % tile != 0:
        raise ValueError(f"T={T} not divisible by assoc tile={tile}")
    mask_f = None if mask is None else mask.astype(jnp.float32)
    if reverse:
        xs = jnp.flip(xs, axis=1)
        mask_f = None if mask_f is None else jnp.flip(mask_f, axis=1)
    _STATS["assoc_traces"] += 1
    (hT, cT), ys = _assoc_core(int(tile), compute_dtype, params, xs, carry,
                               mask_f)
    if reverse:
        ys = jnp.flip(ys, axis=1)
    return (hT, cT), ys
