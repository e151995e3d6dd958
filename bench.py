#!/usr/bin/env python
"""Benchmark harness: all five BASELINE.md configs at REAL model dimensions,
with model-FLOPs and MFU accounting.

Prints ONE JSON line (driver contract): {"metric", "value", "unit",
"vs_baseline"} for the headline config-1 throughput, plus a compact
"configs" map {name: {seq_s, tok_s, tflops, mfu}}. The full per-config
table (dims, flops accounting, measurement notes) is written to
BENCH_TABLE.json next to this file.

Model scale honesty: configs 2-5 are measured at their TRUE
dimensions — vocab 33,278 (WikiText-2) / 50,000 (WikiText-103) embedding +
softmax rows, IMDB bi-LSTM 256 over seq-400, UCI seq2seq over all 370
customer series — with synthetic token/value DATA (no network), which does
not change the compute. MFU uses matmul-only model FLOPs (the standard
accounting: train = 3x forward) against the chip's published bf16 peak.
"""

import datetime
import json
import os
import subprocess
import sys
import time

# STEPS counts K-step DISPATCHES for the headline run (calls = STEPS*K/K):
# sized so one timed rep runs a few seconds, which keeps the fixed cost of
# the closing value fetch (see _two_point) a small share of the rep. The
# CPU baseline subprocess overrides steps=10 explicitly (cpu_baseline),
# unaffected.
B, T, HIDDEN, LAYERS, STEPS, WARMUP = 64, 64, 128, 1, 120, 10
UNROLL = 8  # lax.scan unroll (used by the Pallas backward's recompute scan;
            # the CPU baseline keeps unroll=1, faithful to the reference's
            # step-at-a-time unroll)
K = 512   # steps per dispatch for the TPU run (train/multistep.py): one
          # jitted program runs K optimizer steps, so the per-dispatch
          # host cost amortises. The value was chosen by sweeps on a
          # backend with ~2 ms of fixed cost per dispatch that no longer
          # exists; it has NOT been re-derived on the current chip
          # (ROADMAP C2) — take it as a starting point, from a trace.
          # The CPU baseline keeps one-dispatch-per-step — faithful to
          # the reference's one-Spark-round-per-step structure.
DEVICE_DATA = True  # TPU run stages the corpus in HBM and slices windows
          # on-device (train/device_step.py): per-dispatch host traffic is
          # one scalar. This mirrors the reference's cached-RDD locality
          # (executors iterate a RESIDENT shard; Spark moves only params/
          # grads per round). The CPU baseline keeps the host-fed path.
PALLAS = True  # fused Pallas recurrence kernel for the TPU forward
          # (ops/pallas_lstm.py); the CPU baseline runs lax.scan.
REPS = 3  # report the best rep
# Dispatch is asynchronous: every timed rep ends by fetching a value to the
# host (float(loss)), so the clock stops on finished work, not on the
# enqueue.
_DIR = os.path.dirname(os.path.abspath(__file__))
CACHE = os.path.join(_DIR, "BASELINE_MEASURED.json")
TABLE = os.path.join(_DIR, "BENCH_TABLE.json")

# FLOPs accounting + bf16 peak: ONE source shared with the runtime's
# --log-flops (lstm_tensorspark_tpu/utils/flops.py).
from lstm_tensorspark_tpu.utils.flops import (  # noqa: E402
    PEAK_BF16_TFLOPS,
    TRAIN_FLOPS_MULTIPLIER,
    classifier_fwd_flops_per_token as _classifier_fwd_flops_per_token,
    lm_fwd_flops_per_token as _lm_fwd_flops_per_token,
    seq2seq_fwd_flops_per_seq as _seq2seq_flops_per_seq,
)


# The chain-latency and bandwidth bounds below describe ONE chip; main()
# refuses to measure on any other device (no result, non-zero exit).
DEVICE_KIND = "TPU v5 lite"
PEAK_TFLOPS = PEAK_BF16_TFLOPS[DEVICE_KIND]


# ---------------------------------------------------------------------------
# The five BASELINE.md configs at REAL model dimensions.
# B/T are the measurement batch shapes (documented in BENCH_TABLE.json);
# dims (V/H/L/T) are the config-defining sizes and are NOT scaled down.
# ---------------------------------------------------------------------------
CONFIGS = {
    "ptb_char": dict(kind="lm", V=50, H=128, L=1, B=64, T=64),
    "imdb_bilstm": dict(kind="classifier", V=25_000, H=256, L=1, B=64, T=400),
    # word LMs: bf16 logits (--logits-dtype) — every HBM pass over the
    # [B,T,V] array halves; validated to reach the same ppl target at the
    # same step as f32 (quality_curves comparison in DESIGN round-3 notes)
    "wikitext2": dict(kind="lm", V=33_278, H=650, L=2, B=64, T=35,
                      logits_dtype="bfloat16"),
    "uci_seq2seq": dict(kind="seq2seq", F=370, H=256, L=2, B=64, T=168,
                        horizon=24),
    "wikitext103": dict(kind="lm", V=50_000, H=1024, L=4, B=32, T=64,
                        logits_dtype="bfloat16"),
}


def measure(compute_dtype: str, steps: int, warmup: int, *,
            unroll: int = 1, reps: int = 1, steps_per_call: int = 1,
            device_data: bool = False, use_pallas: bool = False) -> float:
    """Config-1 train-step throughput (seq/sec) on the current default
    backend — the headline metric, kept measurement-identical to round 1.

    ``steps``/``warmup`` count optimizer steps; with ``steps_per_call=K`` they
    are grouped into K-step dispatches. Host-fed mode keeps batch stacking
    inside the timed loop (the feed is part of the step cost);
    ``device_data`` stages the corpus in HBM once (outside the timed loop,
    like Spark's one-time RDD cache) and feeds one scalar per dispatch."""
    import jax

    from lstm_tensorspark_tpu.data import (
        get_dataset, lm_batch_stream, stacked_batches, stage_lm_data,
        window_index_stream,
    )
    from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
    from lstm_tensorspark_tpu.train import (
        make_device_lm_train_step, make_multi_train_step, make_optimizer,
        make_train_step,
    )
    from lstm_tensorspark_tpu.train.loop import init_train_state

    data = get_dataset("ptb_char")
    cfg = LMConfig(
        vocab_size=len(data["vocab"]),
        hidden_size=HIDDEN,
        num_layers=LAYERS,
        compute_dtype=compute_dtype,
        scan_unroll=unroll,
        use_pallas=use_pallas,
    )

    def loss_fn(params, batch, rng):
        return lm_loss(params, batch, cfg)

    opt = make_optimizer("sgd", 0.5)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    state = init_train_state(params, opt, jax.random.PRNGKey(1))

    k = steps_per_call
    if device_data:
        staged = stage_lm_data(data["train"], B, T)
        dstep = make_device_lm_train_step(loss_fn, opt, staged, steps_per_call=k)
        step = lambda s, w0: dstep(s, staged.arrays, w0)  # noqa: E731
        it = window_index_stream(staged, k)
    elif k > 1:
        step = make_multi_train_step(loss_fn, opt)
        it = stacked_batches(lm_batch_stream(data["train"], B, T), k)
    else:
        step = make_train_step(loss_fn, opt)
        it = lm_batch_stream(data["train"], B, T)
    calls, warm_calls = max(steps // k, 1), max(warmup // k, 1)

    for _ in range(warm_calls):
        state, m = step(state, next(it))
    float(m["loss"])  # TRUE barrier (see MEASUREMENT HONESTY above)
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            state, m = step(state, next(it))
        float(m["loss"])  # value fetch = the only trustworthy sync here
        dt = time.perf_counter() - t0
        best = max(best, B * calls * k / dt)
    return best


def _rand_batch(kind: str, c: dict, key):
    """One synthetic batch at REAL model dims (random data, true compute)."""
    import jax
    import jax.numpy as jnp

    B_, T_ = c["B"], c["T"]
    if kind == "lm":
        toks = jax.random.randint(key, (B_, T_ + 1), 0, c["V"], jnp.int32)
        return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    if kind == "classifier":
        return {
            "tokens": jax.random.randint(key, (B_, T_), 0, c["V"], jnp.int32),
            "lengths": jnp.full((B_,), T_, jnp.int32),
            "labels": jax.random.randint(key, (B_,), 0, 2, jnp.int32),
            "valid": jnp.ones((B_,), jnp.float32),
        }
    if kind == "seq2seq":
        k1, k2 = jax.random.split(key)
        return {
            "context": jax.random.normal(k1, (B_, T_, c["F"]), jnp.float32),
            "targets": jax.random.normal(k2, (B_, c["horizon"], c["F"]), jnp.float32),
        }
    raise ValueError(kind)


def measure_config(name: str, *, warmup: int = 64,
                   steps_per_call: int = 32, reps: int = 2) -> dict:
    """Throughput + MFU for one named config at real model dimensions.

    The K-stacked synthetic batch is staged on device ONCE and re-fed every
    dispatch (throughput measurement — the data values don't change the
    compute). Returns the BENCH_TABLE.json record.

    Rep length is SELF-CALIBRATING: a short probe separates the fixed
    cost of a rep (the closing value fetch) from the per-call cost, then
    the timed rep is sized so the fixed cost is <5% of the measurement."""
    import jax
    import jax.numpy as jnp

    from lstm_tensorspark_tpu.train import make_multi_train_step, make_optimizer
    from lstm_tensorspark_tpu.train.loop import init_train_state

    c = CONFIGS[name]
    kind = c["kind"]
    B_, T_ = c["B"], c["T"]

    if kind == "lm":
        from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss

        cfg = LMConfig(vocab_size=c["V"], hidden_size=c["H"],
                       num_layers=c["L"], compute_dtype="bfloat16",
                       logits_dtype=c.get("logits_dtype", "float32"),
                       use_pallas=PALLAS and jax.default_backend() == "tpu")
        params = init_lm(jax.random.PRNGKey(0), cfg)
        loss_fn = lambda p, b, r: lm_loss(p, b, cfg)  # noqa: E731
        fwd_flops_step = _lm_fwd_flops_per_token(c["V"], c["H"], c["L"]) * B_ * T_
        tokens_per_step = B_ * T_
    elif kind == "classifier":
        from lstm_tensorspark_tpu.models import (
            ClassifierConfig, classifier_loss, init_classifier,
        )

        cfg = ClassifierConfig(vocab_size=c["V"], hidden_size=c["H"],
                               num_layers=c["L"], compute_dtype="bfloat16",
                               use_pallas=PALLAS and jax.default_backend() == "tpu")
        params = init_classifier(jax.random.PRNGKey(0), cfg)
        loss_fn = lambda p, b, r: classifier_loss(p, b, cfg)  # noqa: E731
        fwd_flops_step = (
            _classifier_fwd_flops_per_token(c["V"], c["H"], c["L"]) * B_ * T_
        )
        tokens_per_step = B_ * T_
    elif kind == "seq2seq":
        from lstm_tensorspark_tpu.models import (
            Seq2SeqConfig, init_seq2seq, seq2seq_loss,
        )

        cfg = Seq2SeqConfig(num_features=c["F"], hidden_size=c["H"],
                            num_layers=c["L"], horizon=c["horizon"],
                            compute_dtype="bfloat16",
                            use_pallas=PALLAS and jax.default_backend() == "tpu")
        params = init_seq2seq(jax.random.PRNGKey(0), cfg)
        loss_fn = lambda p, b, r: seq2seq_loss(p, b, cfg)  # noqa: E731
        fwd_flops_step = _seq2seq_flops_per_seq(
            c["F"], c["H"], c["L"], T_, c["horizon"]) * B_
        tokens_per_step = B_ * (T_ + c["horizon"])
    else:
        raise ValueError(kind)

    opt = make_optimizer("sgd", 0.1)
    state = init_train_state(params, opt, jax.random.PRNGKey(1))
    step = make_multi_train_step(loss_fn, opt)
    kk = steps_per_call
    batch = _rand_batch(kind, c, jax.random.PRNGKey(2))
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (kk, *a.shape)), batch
    )
    stacked = jax.device_put(stacked)  # staged once, outside the timed loop

    for _ in range(max(warmup // kk, 1)):
        state, m = step(state, stacked)
    float(m["loss"])  # barrier: the value is on the host

    def probe(k):
        nonlocal state, m
        for _ in range(k):
            state, m = step(state, stacked)
        float(m["loss"])

    fixed, per_call = _two_point(probe, 8)
    if per_call is None:  # every probe rep collapsed: be conservative
        fixed, per_call = 0.065, 0.05
    # rep long enough that the fixed cost is <5%, bounded in wall time so a
    # mis-probe can never turn one config into a multi-minute runaway
    calls = int(min(max(20.0 * fixed / per_call, 8), 3000,
                    10.0 / per_call + 1))

    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            state, m = step(state, stacked)
        float(m["loss"])
        dt = time.perf_counter() - t0
        best = max(best, calls * kk / dt)  # optimizer steps / sec

    # fwd + bwd(2x) matmul accounting — the shared policy constant
    train_flops_step = TRAIN_FLOPS_MULTIPLIER * fwd_flops_step
    tflops = best * train_flops_step / 1e12
    rec = {
        "kind": kind,
        "train_flops_step": train_flops_step,
        "dims": {k: v for k, v in c.items() if k != "kind"},
        "seq_per_sec": round(best * B_, 2),
        "tokens_per_sec": round(best * tokens_per_step, 1),
        "model_tflops_per_sec": round(tflops, 3),
        "mfu_vs_bf16_peak": round(tflops / PEAK_TFLOPS, 4),
        "compute_dtype": "bfloat16",
        "steps_per_call": kk,
        "note": "real model dims, synthetic data; train FLOPs = 3x fwd matmuls",
    }
    return rec


def _two_point(run, n: int, reps: int = 3):
    """Split a rep's fixed dispatch+fetch latency from real per-call
    cost: ``t1 = fixed + d``, ``tn = fixed + n*d`` ⇒ ``d = (tn-t1)/(n-1)``.

    ``run(k)`` must execute k queued dispatches then fetch one value. The
    difference estimator is noise-sensitive (fixed-latency jitter can rival
    the signal), so each probe repeats ``reps`` times, reps where the
    difference collapses (tn <= t1: a latency spike ate the signal) are
    REJECTED, and the MEDIAN d wins — min-of-reps would select the
    worst-case underestimate. Returns (fixed, d), or (None, None) when
    every rep collapsed (caller must treat the probe as failed)."""
    pairs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run(1)
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(n)
        tn = time.perf_counter() - t0
        if tn > t1:
            pairs.append(((tn - t1) / (n - 1), t1))
    if not pairs:
        return None, None
    d = sorted(p[0] for p in pairs)[len(pairs) // 2]
    t1_med = sorted(p[1] for p in pairs)[len(pairs) // 2]
    return max(t1_med - d, 0.0), d


def measure_roofline(name: str, *, chains: int = 256, reps: int = 3) -> dict:
    """Sequential-recurrence roofline for one config.

    An LSTM train step cannot beat its DEPENDENT chain: T forward steps of
    ``h @ U`` + gates, then the T-step cotangent chain backward — no batching
    or fusion removes that serialization. The bound is built from MEASURED
    latency, not FLOPs: ``chain_sec`` times the fastest implementation we
    have of the full gated chain (the fused Pallas forward at this config's
    local (B, H, T_chain)), k-chained hT→h0 inside ONE jitted fori_loop so
    the dispatch cost amortises away. Then

        bound_sec = 2*chain_sec                (fwd chain + bwd chain)
                  + (train_flops - 3*chain_flops) / peak   (everything else,
                    assumed perfectly parallel — other layers/directions
                    COULD overlap the chain, so the bound is a true floor)

    and ``fraction_of_bound = bound_sec / measured_sec_per_step``: 1.0 means
    the step runs AT the recurrence bound — the remaining MFU gap is the
    serial chain's arithmetic-intensity floor, not implementation slack.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lstm_tensorspark_tpu.ops import init_lstm_params
    from lstm_tensorspark_tpu.ops.pallas_lstm import pallas_lstm_scan, supported

    c = CONFIGS[name]
    B_, H_ = c["B"], c["H"]
    kind = c["kind"]
    # critical-path length: layers/directions can pipeline (path T + L - 1
    # ≈ T); the seq2seq decoder chain EXTENDS the encoder's (dependent)
    T_chain = c["T"] + (c["horizon"] if kind == "seq2seq" else 0)
    if not supported(B_, H_):
        return {"error": f"no fused kernel plan for B={B_}, H={H_}"}

    D = 32  # input width is irrelevant to the chain; keep xproj tiny
    params = init_lstm_params(jax.random.PRNGKey(0), D, H_)
    xs = jax.random.normal(jax.random.PRNGKey(1), (B_, T_chain, D))

    def chained(params, xs, h0, c0):
        def body(_, carry):
            (hT, cT), _ys = pallas_lstm_scan(
                params, xs, carry, compute_dtype=jnp.bfloat16
            )
            return (hT, cT)
        hT, cT = lax.fori_loop(0, chains, body, (h0, c0))
        return hT, cT, jnp.sum(hT)  # sum on-device: ONE tiny fetch suffices

    h0 = jnp.zeros((B_, H_), jnp.float32)
    c0 = jnp.zeros((B_, H_), jnp.float32)
    run = jax.jit(chained)
    # A dispatch plus a value fetch has a FIXED cost far above one chain's
    # own, which poisons naive division; `_two_point` removes it with a
    # median-robust calibration, and `chains` is large enough that the
    # per-dispatch overhead is a small share of the signal.
    hT, cT, s = run(params, xs, h0, c0)
    float(s)  # warm + barrier

    def probe(k):
        out = None
        for _ in range(k):
            out = run(params, xs, h0, c0)
        float(out[2])

    _, d = _two_point(probe, 16, reps=reps)
    if d is None:
        return {"error": "calibration collapsed (latency jitter ate the "
                         "signal in every probe rep)"}
    chain_sec = d / chains
    chain_flops = 8.0 * B_ * H_ * H_ * T_chain  # the chain's h@U matmuls
    return {
        "chain": {"B": B_, "H": H_, "T": T_chain},
        "chain_sec": chain_sec,
        "per_step_latency_us": round(chain_sec / T_chain * 1e6, 3),
        "chain_flops": chain_flops,
    }


def measure_hbm_bw(mb: int = 128, iters: int = 8, reps: int = 3) -> dict:
    """Measured HBM bandwidth: an elementwise pass over a ``mb``-MiB f32
    array, ``iters``-chained inside ONE jitted fori_loop (each iteration
    reads + writes the full array — the carry dependency stops XLA fusing
    across iterations, so every pass is real HBM traffic). `_two_point`
    strips the fixed dispatch+fetch latency as everywhere else.
    This is the denominator of the r4 bandwidth bound — measured on THIS
    chip, not a datasheet number."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = mb * 2**20 // 4
    x = jnp.arange(n, dtype=jnp.float32) * 1e-6  # not constant-foldable

    def body(_, a):
        return a * 1.0000001 + 1.0

    run = jax.jit(lambda a: lax.fori_loop(0, iters, body, a))
    y = run(x)
    float(y[0])  # warm + barrier

    def probe(k):
        out = x
        for _ in range(k):
            out = run(out)
        float(out[0])

    _, d = _two_point(probe, 4, reps=reps)
    if d is None:
        return {"error": "calibration collapsed (latency jitter)"}
    moved = 2.0 * n * 4 * iters  # read + write per iteration
    return {
        "array_mib": mb,
        "iters": iters,
        "gb_per_sec": round(moved / d / 1e9, 2),
    }


def _scan_stream_bytes(strategy: str, T_s: int, D_s: int, B: int, H: int,
                       pbytes: int) -> float:
    """Estimated HBM bytes ONE optimizer step moves for ONE sequential
    scan under ``strategy`` — the numerator of the r4 bandwidth bound.

    Inventory (A = T_s*B rows; r = stream-dtype bytes, 4 = f32):
    resident/tiled — fwd: xs read (f32, by the xproj producer), xproj
    write+read (r), ys write, z write (r), cs write; bwd kernel: z read
    (r), dys + cs reads, dz write (r); outside: dz read 4x (dU, dW, db,
    dxs — separate contractions), ys read (h_prev for dU), xs read
    (dW), dxs write. tiled additionally RE-STREAMS U every step (fwd)
    and U^T (bwd) — the strategy's defining cost at H where U exceeds
    VMEM. residentx — no xproj/z anywhere: xs streamed once per kernel
    (r) in fwd AND bwd (z recomputed in-kernel), cs the only residual;
    same dz and outside traffic. Estimates deliberately EXCLUDE the
    non-scan model (embedding/head/optimizer) — those FLOPs-side costs
    sit in the impl bound's parallel term; mask streams are negligible
    (LANE wide). An estimate, not a meter: good to ~10-20%, enough to
    say which side of the bandwidth roof a config sits on."""
    from lstm_tensorspark_tpu.ops.pallas_lstm import _pad_to_lane, _rbytes

    r = _rbytes(pbytes)
    A = T_s * B
    Hp = _pad_to_lane(H)
    z4 = A * 4 * Hp  # elements of one [T,B,4H] stream
    s1 = A * Hp      # elements of one [T,B,H] stream
    xs_f32 = A * D_s * 4
    dz_outside = 4 * z4 * r + s1 * 4 + xs_f32 + A * D_s * 4  # dU/dW/db/dxs
    if strategy == "residentx":
        xs_r = A * _pad_to_lane(D_s) * r
        fwd = xs_r + s1 * 4 * 2            # xs in; ys + cs out
        bwd = xs_r + s1 * 4 * 2 + z4 * r   # xs + dys + cs in; dz out
        return fwd + bwd + dz_outside
    fwd = xs_f32 + z4 * r * 2 + s1 * 4 + z4 * r + s1 * 4  # xproj w+r, ys, z, cs
    bwd = z4 * r + s1 * 4 * 2 + z4 * r                    # z, dys, cs in; dz out
    total = fwd + bwd + dz_outside
    if strategy == "tiled":
        total += T_s * 2 * 4 * Hp * Hp * pbytes  # U fwd + U^T bwd re-streamed
    return total


def _config_scans(name: str) -> list:
    """(T, input_width, has_mask, dirs) for EVERY sequential scan one
    optimizer step of this config runs — the per-scan inventory
    `_impl_bound` plans over. ``dirs=2`` marks a scan the runtime runs
    through the stacked-direction kernel (both bi-LSTM chains advance in
    ONE serialized pass; traffic of two). LM: embed output (width H)
    feeds layer 0, H feeds deeper layers (models/lstm_lm.py).
    Classifier: two directions per layer; embed (width H) feeds layer 0,
    the 2H direction-concat feeds deeper layers (models/classifier.py:61).
    Seq2seq: encoder scans at T then decoder scans at horizon, F feeding
    both layer 0s (models/seq2seq.py:48-51)."""
    c = CONFIGS[name]
    kind, H_, L_ = c["kind"], c["H"], c["L"]
    if kind == "lm":
        return [(c["T"], H_, False, 1)] * L_
    if kind == "classifier":
        # mirror the runtime's dispatch (ops/scan.py bidir_lstm_scan): a
        # layer whose shape fits the stacked-direction kernel advances
        # BOTH chains in one pass — one serialized scan, but the traffic
        # of two (the stacked entry below carries dirs=2 for the
        # bandwidth accounting). Honors the same A/B lever.
        import os

        from lstm_tensorspark_tpu.ops.pallas_bilstm import bilstm_supported

        pbytes = 2 if c.get("compute_dtype", "bfloat16") == "bfloat16" else 4
        fuse_ok = os.environ.get("LSTM_TSP_NO_BIDIR_FUSE") != "1"
        scans = []
        for layer in range(L_):
            D = H_ if layer == 0 else 2 * H_
            if fuse_ok and bilstm_supported(
                    c["B"], H_, D, c["T"], platform="tpu",
                    param_dtype_bytes=pbytes, has_mask=True):
                scans.append((c["T"], D, True, 2))  # stacked: dirs share
            else:
                scans += [(c["T"], D, True, 1)] * 2  # two serialized scans
        return scans
    if kind == "seq2seq":
        def width(layer):
            return c["F"] if layer == 0 else H_
        return ([(c["T"], width(l), False, 1) for l in range(L_)]
                + [(c["horizon"], width(l), False, 1) for l in range(L_)])
    raise ValueError(kind)


def _impl_bound(name: str, rl: dict, rec: dict, measured: float) -> dict:
    """Strategy-aware serialized-chain bound for one measured config.

    Counts the sequential in-chain steps THIS implementation runs per
    optimizer step, each costing ~chain_sec/T_chain (every in-chain MXU
    op — ``h@U``, z recompute, ``dz@U^T`` — moves the same 8BH² FLOPs
    per step, so per-step chain latency is the right unit): each scan
    contributes its OWN length times (1 + its backward strategy's
    in-chain multiplier). dU/dW/dxs are OUTSIDE the chain (contracted
    from streamed dz) and so stay in the parallel term. ``measured`` is
    the UNROUNDED s/step (the rounded copy in ``rl`` would skew the
    fraction by up to 0.6% at config-1 step times).

    Per-scan derivation: the strategy comes from the
    runtime's own `chosen_bwd_strategy` evaluated at EACH scan's
    (T, input width) — a heterogeneous config (seq2seq's short-horizon
    decoder, a stacked classifier whose layer-1 input is 2H) no longer
    inherits the layer-0 label. When every scan plans the same strategy
    the legacy `impl_bwd_strategy` string is that name; otherwise it is
    "mixed" and `impl_bwd_strategies` carries the per-strategy scan
    counts."""
    from lstm_tensorspark_tpu.ops.pallas_lstm import (
        _FUSEDX_MIN_T, _pad_to_lane, chosen_bwd_strategy,
    )

    c = CONFIGS[name]
    B_, H_ = c["B"], c["H"]
    kind = c["kind"]
    Hp = _pad_to_lane(H_)
    # pbytes from the config's compute dtype, exactly as the runtime gate
    # derives it from the fused kernel dtype (all table configs are bf16
    # today; an f32 row would flip the VMEM plans at 4 bytes)
    pbytes = 2 if c.get("compute_dtype", "bfloat16") == "bfloat16" else 4
    MULT = {"residentx": 2, "resident": 1, "tiled": 1, "recompute": 2}
    serial_steps = 0
    stream_bytes = 0.0
    strategy_counts: dict = {}
    for T_s, D_s, has_mask, dirs in _config_scans(name):
        if dirs == 2:
            # stacked-direction kernel (ops/pallas_bilstm.py): residentx
            # pair by construction — ONE serialized chain of T steps for
            # both directions, traffic of two residentx scans (2B rows)
            s = "residentx"
            stream_bytes += 2 * _scan_stream_bytes(s, T_s, D_s, B_, H_,
                                                   pbytes)
        else:
            Dp = _pad_to_lane(D_s) if T_s >= _FUSEDX_MIN_T else None
            s = chosen_bwd_strategy(B_, T_s, Hp, pbytes,
                                    has_mask=has_mask, Dp=Dp)
            stream_bytes += _scan_stream_bytes(s, T_s, D_s, B_, H_, pbytes)
        serial_steps += T_s * (1 + MULT[s])
        strategy_counts[s] = strategy_counts.get(s, 0) + 1
    # chain-latency units: the roofline's chain covers T_chain steps
    T_chain = c["T"] + (c["horizon"] if kind == "seq2seq" else 0)
    passes = serial_steps / T_chain
    parallel = max(
        rec["train_flops_step"] - passes * rl["chain_flops"], 0.0
    ) / (PEAK_TFLOPS * 1e12)
    bound = passes * rl["chain_sec"] + parallel
    out = {
        "impl_serial_steps": serial_steps,
        "impl_serial_passes": round(passes, 4),
        "impl_bwd_strategy": (next(iter(strategy_counts))
                              if len(strategy_counts) == 1 else "mixed"),
        "impl_bound_sec_per_step": round(bound, 6),
        "fraction_of_impl_bound": round(bound / measured, 4),
        # numerator of the r4 bandwidth bound (estimate; see
        # _scan_stream_bytes) — main() divides by the MEASURED HBM BW and
        # publishes the max(compute-bound, bandwidth-bound) floor
        "stream_bytes_per_step": int(stream_bytes),
    }
    if len(strategy_counts) > 1:
        out["impl_bwd_strategies"] = strategy_counts
    return out


def measure_generation(*, new_tokens: int = 512, batch: int = 64,
                       reps: int = 3) -> dict:
    """Autoregressive decode throughput (the inference surface, SURVEY.md §2
    "Eval / inference" row): config-1-class LM, batched greedy decode of
    ``new_tokens`` continuations in ONE jitted prefill+decode program
    (models/generate.py). Tokens/sec counts generated tokens only."""
    import jax
    import jax.numpy as jnp

    from lstm_tensorspark_tpu.models import LMConfig, init_lm, make_generate_fn

    cfg = LMConfig(vocab_size=50, hidden_size=HIDDEN, num_layers=LAYERS,
                   compute_dtype="bfloat16")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    gen = make_generate_fn(cfg, max_new_tokens=new_tokens, greedy=True)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, 32), 0, 50,
                                jnp.int32)
    rng = jax.random.PRNGKey(2)
    out = gen(params, prompt, rng)
    int(out[0, -1])  # barrier

    def probe(k):
        o = None
        for _ in range(k):
            o = gen(params, prompt, rng)
        int(o[0, -1])

    _, d = _two_point(probe, 8, reps=reps)
    if d is None:
        return {"error": "calibration collapsed (latency jitter)"}
    return {
        "model": {"V": 50, "H": HIDDEN, "L": LAYERS},
        "batch": batch,
        "prompt_len": 32,
        "new_tokens": new_tokens,
        "decode": "greedy, single jitted prefill+decode program",
        "tokens_per_sec": round(batch * new_tokens / d, 1),
        "sec_per_token_per_seq": round(d / new_tokens * 1e6, 2),
    }


def measure_pp_config5(*, steps: int = 48, warmup: int = 8) -> dict:
    """Config-5-shape (H=1024, L=4) training under the PIPELINE wavefront,
    fused Pallas stage interiors vs plain lax.scan.

    One real chip ⇒ a pp=1 mesh: the full shard_map wavefront machinery runs
    (manual axes, ppermute elided at S=1), so the measured delta isolates
    the stage-interior kernel — the part that scales to real pp>1 meshes
    unchanged (stage interiors are collective-free). Single-step dispatches
    (the PP step has no K-step variant), so per-dispatch overhead is part
    of both numbers; noted in the record."""
    import jax
    import jax.numpy as jnp

    from lstm_tensorspark_tpu.models import LMConfig, init_lm
    from lstm_tensorspark_tpu.parallel import make_mesh
    from lstm_tensorspark_tpu.parallel.pipeline_parallel import (
        make_pp_lm_train_step, place_pp_lm_params, stack_lm_params,
    )
    from lstm_tensorspark_tpu.train import make_optimizer
    from lstm_tensorspark_tpu.train.loop import init_train_state

    c = CONFIGS["wikitext103"]
    B_, T_ = c["B"], c["T"]

    def run(use_pallas: bool) -> float:
        cfg = LMConfig(vocab_size=c["V"], hidden_size=c["H"],
                       num_layers=c["L"], compute_dtype="bfloat16",
                       logits_dtype=c.get("logits_dtype", "float32"),
                       use_pallas=use_pallas)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        opt = make_optimizer("sgd", 0.1)
        mesh = make_mesh(dp=1, pp=1)
        stacked = stack_lm_params(params)
        placed = place_pp_lm_params(stacked, mesh)
        step = make_pp_lm_train_step(cfg, opt, mesh, stacked, microbatches=2)
        state = init_train_state(placed, opt, jax.random.PRNGKey(1))
        toks = jax.random.randint(jax.random.PRNGKey(2), (B_, T_ + 1), 0,
                                  c["V"], jnp.int32)
        batch = jax.device_put(
            {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
        )
        for _ in range(warmup):
            state, m = step(state, batch)
        float(m["loss"])  # barrier
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, batch)
        float(m["loss"])
        return steps / (time.perf_counter() - t0)

    scan_sps = run(False)
    pallas_sps = run(True)
    return {
        "shape": {k: v for k, v in c.items() if k != "kind"},
        "mesh": "dp=1,pp=1 (one chip; wavefront machinery live, ppermute "
                "elided at S=1)",
        "microbatches": 2,
        "scan_seq_per_sec": round(scan_sps * B_, 2),
        "pallas_seq_per_sec": round(pallas_sps * B_, 2),
        "pallas_speedup": round(pallas_sps / scan_sps, 3),
        "note": "single-step dispatches; dispatch overhead in both numbers",
    }


def cpu_baseline() -> float:
    """Single-process CPU float32 reference throughput, cached."""
    if os.path.exists(CACHE):
        with open(CACHE) as f:
            return json.load(f)["cpu_seq_per_sec"]
    # fresh interpreter so the CPU platform can be forced cleanly
    code = (
        "import bench;"
        "print('CPUBASE', bench.measure('float32', steps=10, warmup=2))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=_DIR, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    line = [l for l in out.stdout.splitlines() if l.startswith("CPUBASE")]
    if not line:
        raise RuntimeError(f"cpu baseline failed: {out.stderr[-2000:]}")
    value = float(line[0].split()[1])
    with open(CACHE, "w") as f:
        json.dump({"cpu_seq_per_sec": value, "config": {
            "B": B, "T": T, "hidden": HIDDEN, "layers": LAYERS,
            "dtype": "float32", "note": "single-process CPU stand-in for Spark-CPU baseline",
        }}, f, indent=1)
    return value


def require_chip() -> dict:
    """The device this run measures, as JAX reports it — and NO result
    when it is not the chip the bounds describe: a number from any other
    device must never be printed under this benchmark's metric names."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or device["kind"] != DEVICE_KIND:
        raise SystemExit(
            f"bench.py measures one {DEVICE_KIND!r} chip; JAX found "
            f"{device}. No result.")
    return device


def main() -> int:
    device = require_chip()
    baseline = cpu_baseline()
    try:
        hbm = measure_hbm_bw()
    except Exception as e:  # the BW probe failing must not kill the bench
        hbm = {"error": f"{type(e).__name__}: {e}"}
    value = measure(
        "bfloat16", STEPS * K, WARMUP * K,
        unroll=UNROLL, reps=REPS, steps_per_call=K, device_data=DEVICE_DATA,
        use_pallas=PALLAS,
    )

    table = {}
    compact = {}
    for name in CONFIGS:
        try:
            # ptb_char's post-indexing-fix step (~78 us device) is host-
            # bound at 32-step dispatches; the bigger configs are device-
            # bound at K=32 already (>= 1 ms/step)
            rec = measure_config(
                name, steps_per_call=K if name == "ptb_char" else 32)
        except Exception as e:  # a config failing must not kill the headline
            rec = {"error": f"{type(e).__name__}: {e}"}
        if "error" not in rec:
            # sequential-recurrence roofline: is the residual MFU gap
            # implementation slack or the chain's latency floor?
            try:
                rl = measure_roofline(name)
            except Exception as e:
                rl = {"error": f"{type(e).__name__}: {e}"}
            if "error" not in rl:
                measured = CONFIGS[name]["B"] / rec["seq_per_sec"]  # s/step
                parallel = max(
                    rec["train_flops_step"]
                    - TRAIN_FLOPS_MULTIPLIER * rl["chain_flops"], 0.0
                ) / (PEAK_TFLOPS * 1e12)
                bound = 2.0 * rl["chain_sec"] + parallel
                rl.update(
                    measured_sec_per_step=round(measured, 6),
                    bound_sec_per_step=round(bound, 6),
                    fraction_of_bound=round(bound / measured, 4),
                )
                # Second, STRATEGY-AWARE bound: the floor above assumes one
                # fwd + one bwd chain with everything else perfectly
                # parallel. THIS implementation serializes layers,
                # directions, and the chosen backward kernel's in-chain MXU
                # ops (residentx recomputes z: 2 chain-latency units/step;
                # resident/tiled stream z: 1; recompute fallback re-runs
                # the forward: 2). fraction_of_impl_bound ≈ 1 therefore
                # means "the step runs at the speed of ITS OWN serialized
                # structure" — remaining MFU gap is the structure, not
                # kernel slack; the gap between the two bounds is the
                # (theoretical) prize for overlapping layers/directions.
                try:
                    rl.update(_impl_bound(name, rl, rec, measured))
                    # r4 bandwidth floor: a step can be slower than its
                    # serialized-chain bound simply because its residual
                    # streams saturate HBM. The COMBINED floor is the max
                    # of the two; fraction ≈ 1 against it means the step
                    # runs at the speed of its own structure AND traffic.
                    if "gb_per_sec" in hbm:
                        bw_sec = (rl["stream_bytes_per_step"]
                                  / (hbm["gb_per_sec"] * 1e9))
                        bound2 = max(rl["impl_bound_sec_per_step"], bw_sec)
                        rl.update(
                            bw_bound_sec_per_step=round(bw_sec, 6),
                            bound_binding=("bandwidth"
                                           if bw_sec
                                           > rl["impl_bound_sec_per_step"]
                                           else "serial-chain"),
                            impl_bound2_sec_per_step=round(bound2, 6),
                            fraction_of_impl_bound2=round(
                                bound2 / measured, 4),
                        )
                except Exception as e:
                    rl["impl_bound_error"] = f"{type(e).__name__}: {e}"
            rec["roofline"] = rl
        table[name] = rec
        if "error" not in rec:
            compact[name] = {
                "seq_s": rec["seq_per_sec"],
                "tok_s": rec["tokens_per_sec"],
                "tflops": rec["model_tflops_per_sec"],
                "mfu": rec["mfu_vs_bf16_peak"],
                "bound_frac": rec["roofline"].get("fraction_of_bound"),
            }
        else:
            compact[name] = rec
    try:
        pp_rec = measure_pp_config5()
    except Exception as e:  # PP delta failing must not kill the headline
        pp_rec = {"error": f"{type(e).__name__}: {e}"}
    try:
        gen_rec = measure_generation()
    except Exception as e:
        gen_rec = {"error": f"{type(e).__name__}: {e}"}
    try:
        head = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=_DIR, timeout=30).stdout.strip() or None
    except Exception:
        head = None
    with open(TABLE, "w") as f:
        json.dump({
            "peak_tflops_bf16": PEAK_TFLOPS,
            "hbm_bandwidth": hbm,
            "headline_seq_per_sec": round(value, 2),
            "vs_cpu_baseline": round(value / baseline, 2),
            # self-describing provenance (git history would misattribute
            # a fresh uncommitted table to the PREVIOUS measurement's
            # commit)
            "captured_at": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            "measured_at_commit": head,
            "configs": table,
            "pp_pallas_config5": pp_rec,
            "generation": gen_rec,
        }, f, indent=1)

    print(json.dumps({
        "device": device,
        "metric": "ptb_char_lstm_train_seq_per_sec_per_chip",
        "value": round(value, 2),
        "unit": "seq/sec",
        "vs_baseline": round(value / baseline, 2),
        "configs": compact,
        "pp_pallas_speedup_config5": pp_rec.get("pallas_speedup"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
