"""Single-program training loop: jitted train step + host-side epoch driver.

Reference parity: SURVEY.md §3.1 — the reference's outer hot loop is
broadcast(params) → mapPartitions(train_partition) → treeAggregate(grads) →
driver update, with full param/grad serialization over TCP each round. Here
the whole round is ONE jitted XLA program: forward, BPTT (jax.grad), and the
optimizer update run on-device; the host only sees scalar metrics. Under the
data-parallel backend (parallel/data_parallel.py) the same step body runs
under shard_map with a psum in place of treeAggregate (SURVEY.md §3.3).

Buffer donation (`donate_argnums=0`) reuses the parameter/optimizer memory
across steps — the rebuilt equivalent of "weights live on-device, zero host
round-trips per step" (SURVEY.md §2 native-capability table).
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable, Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import obs
from ..ops import parallel_scan as _pscan
from ..resilience import faults as _faults
from ..utils.tracing import span
from .sharded_update import WHOLE, DpReduce


class AnomalousTrainingError(RuntimeError):
    """Raised by :func:`train_loop` after ``anomaly_limit`` CONSECUTIVE
    non-finite steps: the model is diverged (or the data/hardware is
    producing garbage) and continuing would only burn budget skipping
    updates. The CLI maps it to ``resilience.exit_codes.ANOMALY_RC`` so the
    supervisor restarts from the last checkpoint — whose params are clean,
    because the guard skipped every anomalous update."""

    def __init__(self, consecutive: int, total: int, step: int):
        self.consecutive = consecutive
        self.total = total
        self.step = step
        super().__init__(
            f"{consecutive} consecutive non-finite steps at step {step} "
            f"({total} anomalous total); aborting for supervisor restart"
        )


class TrainState(NamedTuple):
    step: jax.Array  # scalar int32
    params: Any
    opt_state: Any
    rng: jax.Array
    # Recurrent state carried across contiguous windows (stateful truncated
    # BPTT). None for stateless training; per-layer (h, c) otherwise.
    carries: Any = None


def init_train_state(params, optimizer, rng, *, carries=None) -> TrainState:
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=optimizer.init(params),
        rng=rng,
        carries=carries,
    )


def call_loss(loss_fn, params, batch, rng, carries, *, stateful: bool):
    """Uniform invocation of the (stateless|stateful) loss_fn signature."""
    if stateful:
        return loss_fn(params, batch, rng, carries)
    return loss_fn(params, batch, rng)


def accumulate_grads(loss_fn, params, batch, rng, *, grad_accum: int):
    """Microbatched gradient accumulation: split the (per-shard) batch into
    ``grad_accum`` equal microbatches along the leading axis and `lax.scan`
    value_and_grad over them, keeping a running mean of grads and loss.

    Peak activation memory drops to one microbatch's worth (the BPTT
    activations of [B/N, T] instead of [B, T]) at the cost of N sequential
    grad passes — the standard large-model trade. Equal microbatch sizes make
    the mean-of-means exactly the full-batch mean, so the update is
    numerically the full-batch update (tests/test_grad_accum.py)."""
    micro = jax.tree.map(
        lambda a: a.reshape(grad_accum, a.shape[0] // grad_accum, *a.shape[1:]),
        batch,
    )

    def body(acc, inp):
        i, mb = inp
        (loss, _), grads = jax.value_and_grad(
            lambda p: call_loss(
                loss_fn, p, mb, jax.random.fold_in(rng, i), None, stateful=False
            ),
            has_aux=True,
        )(params)
        g_acc, l_acc = acc
        g_acc = jax.tree.map(lambda a, b: a + b / grad_accum, g_acc, grads)
        return (g_acc, l_acc + loss / grad_accum), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (grads, loss), _ = jax.lax.scan(
        body, (zero, jnp.zeros((), jnp.float32)), (jnp.arange(grad_accum), micro)
    )
    return loss, grads


def step_body(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    state: TrainState,
    batch,
    *,
    stateful: bool = False,
    rng_transform: Callable | None = None,
    reduce_fn: Callable | None = None,
    grad_accum: int = 1,
):
    """The ONE train-step body shared by the single-chip and data-parallel
    paths (keeps them provably identical — test_dp.py's loss-parity relies on
    it). ``rng_transform`` perturbs the per-step dropout key (DP folds in the
    shard index); ``reduce_fn(grads, loss)`` inserts the cross-shard mean
    (DP: `DpReduce` — the treeAggregate replacement, under which a large
    leaf arrives, is updated and leaves as one chip's share); ``grad_accum > 1``
    microbatches the gradient computation (stateless losses only — recurrent
    carries are batch-aligned and do not split)."""
    rng, sub = jax.random.split(state.rng)
    if rng_transform is not None:
        sub = rng_transform(sub)
    # Under DP a large leaf lives as this chip's share of it
    # (train/sharded_update.py): gather the whole parameters for the forward
    # and backward; the reduction hands such a leaf's gradient back as the
    # share, and the update below runs on shares. With no such leaf (and off
    # DP) every `part` method is the plain form.
    part = reduce_fn.part if isinstance(reduce_fn, DpReduce) else WHOLE
    whole = part.gather(state.params)
    if grad_accum > 1:
        if stateful:
            raise ValueError("grad_accum is not supported with stateful TBPTT")
        loss, grads = accumulate_grads(
            loss_fn, whole, batch, sub, grad_accum=grad_accum
        )
        carries = state.carries
    else:
        (loss, aux), grads = jax.value_and_grad(
            lambda p: call_loss(
                loss_fn, p, batch, sub, state.carries, stateful=stateful
            ),
            has_aux=True,
        )(whole)
        carries = jax.lax.stop_gradient(aux["carries"]) if stateful else state.carries
    grads = _faults.tamper_grads(grads, state.step)  # identity when unarmed
    if reduce_fn is not None:
        grads, loss = reduce_fn(grads, loss)
    gnorm = part.global_norm(grads)
    updates, opt_state = optax.with_extra_args_support(optimizer).update(
        grads, state.opt_state, state.params, global_norm=gnorm)
    params = optax.apply_updates(state.params, updates)
    # Non-finite guard: a NaN/Inf loss or gradient must not poison the
    # params/optimizer moments (one bad batch would otherwise end the run —
    # every later step inherits the NaNs). Skip the whole update (params,
    # moments, AND carries — a diverged forward pass taints the recurrent
    # state too), advance step/rng so the budget and data order hold, and
    # surface the skip as metrics["anomalous"] for the host loop to count.
    # Under DP the guard decision is uniform across shards: the loss is
    # pmean'd and the norm is the global one before the check.
    finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
    keep = lambda new, old: jnp.where(finite, new, old)  # noqa: E731
    params = jax.tree.map(keep, params, state.params)
    opt_state = jax.tree.map(keep, opt_state, state.opt_state)
    if stateful:
        carries = jax.tree.map(keep, carries, state.carries)
    metrics = {
        "loss": loss,
        "grad_norm": gnorm,
        "anomalous": (~finite).astype(jnp.float32),
    }
    return TrainState(state.step + 1, params, opt_state, rng, carries), metrics


def dp_rng_transform(axis: str = "data"):
    """Per-shard dropout-key perturbation: fold the shard index into the
    step key (distinct dropout per shard, common everything else). The ONE
    definition shared by every DP step builder (parallel/data_parallel.py,
    multistep.py, device_step.py). Lives here — the dependency-free base
    module — to avoid train↔parallel import cycles."""
    return lambda sub: jax.random.fold_in(sub, jax.lax.axis_index(axis))


def dp_reduce_fn(part) -> DpReduce:
    """The gradient reduction of every DP step builder, for the `Partition`
    that `dp_shard_map` hands its per-shard function: see `DpReduce`
    (train/sharded_update.py), where the contract lives."""
    return DpReduce(part)


def summarize_scan_metrics(ms) -> dict:
    """Reduce per-step metrics stacked by a K-step `lax.scan` to the logging
    contract shared by every multi-step path (multistep.py, device_step.py):
    ``loss`` = mean over the K steps, ``loss_last``/``grad_norm`` = final
    step's, ``anomalous`` (when the body reports it) = COUNT of skipped
    (non-finite) steps in the window."""
    out = {
        "loss": jnp.mean(ms["loss"]),
        "loss_last": ms["loss"][-1],
        "grad_norm": ms["grad_norm"][-1],
    }
    if "anomalous" in ms:
        out["anomalous"] = jnp.sum(ms["anomalous"])
    return out


def make_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    jit: bool = True,
    donate: bool = True,
    stateful: bool = False,
    grad_accum: int = 1,
):
    """Build the jitted step.

    Stateless (default): ``loss_fn(params, batch, dropout_rng) -> (loss, aux)``.
    Stateful TBPTT (``stateful=True``): ``loss_fn(params, batch, dropout_rng,
    carries) -> (loss, aux)`` with ``aux["carries"]`` the final recurrent
    state; it is gradient-stopped and fed to the next window (truncated BPTT
    over the contiguous stream — SURVEY.md §5 "Long-context" row).
    """

    def train_step(state: TrainState, batch):
        return step_body(
            loss_fn, optimizer, state, batch,
            stateful=stateful, grad_accum=grad_accum,
        )

    if jit:
        train_step = jax.jit(train_step, donate_argnums=(0,) if donate else ())
    return train_step


def make_eval_step(loss_fn: Callable, *, jit: bool = True, stateful: bool = False):
    """Forward-only step (SURVEY.md §3.4): loss, no grads, no update.

    Stateful variant returns ``({"loss": ...}, carries)`` so evaluation can
    carry recurrent state across contiguous windows."""

    def _metrics(loss, aux):
        # Token count for exact token-weighted averaging in evaluate();
        # losses are per-token means, so the cross-batch mean must be
        # weighted by tokens to stay exact under unequal batch sizes
        # (dropped remainders, variable-length buckets).
        m = {"loss": loss}
        if isinstance(aux, dict) and "tokens" in aux:
            m["tokens"] = aux["tokens"]
        return m

    if stateful:

        def eval_step(params, batch, carries):
            loss, aux = loss_fn(params, batch, None, carries)
            return _metrics(loss, aux), aux["carries"]

    else:

        def eval_step(params, batch):
            loss, aux = loss_fn(params, batch, None)
            return _metrics(loss, aux)

    if jit:
        eval_step = jax.jit(eval_step)
    return eval_step


def evaluate(
    eval_step, params, batches: Iterable, *, carries=None
) -> dict[str, float]:
    """Token-weighted mean loss + perplexity over batches. Pass ``carries``
    (with a stateful eval_step) to thread recurrent state through the
    contiguous stream.

    Batch losses are weighted by their token count (when the loss aux
    reports one) so perplexity is the exact corpus-level value under any
    batching — equal-size batches, dropped remainders, or variable-length
    buckets all give the same answer."""
    stateful = carries is not None
    # keep every batch's metric HANDLES and fetch once after the loop:
    # float(...) inside the loop would block on each batch's device
    # program (B host round-trips per eval sweep), serializing dispatch
    # with readback exactly like per-token decode used to. The handles
    # are O(1) scalars each, so holding B of them is free.
    handles = []
    for batch in batches:
        if stateful:
            m, carries = eval_step(params, batch, carries)
        else:
            m = eval_step(params, batch)
        handles.append(m)
    total, weight = 0.0, 0.0
    for m in jax.device_get(handles):
        w = float(m["tokens"]) if "tokens" in m else 1.0
        total += float(m["loss"]) * w
        weight += w
    loss = total / max(weight, 1.0)
    return eval_metrics(loss)


def eval_metrics(loss: float) -> dict[str, float]:
    """The ONE loss→metrics mapping shared by host-side `evaluate()` and the
    fused on-device eval (device_step.py) so their records are comparable."""
    # math.exp, not jnp.exp: the jnp spelling dispatched a whole device
    # program (and a blocking readback) to exponentiate ONE host scalar
    # on every eval record
    loss = float(loss)
    return {
        "eval_loss": loss,
        "eval_ppl": math.exp(min(loss, 30.0)),
    }


def train_loop(
    state: TrainState,
    train_step: Callable,
    batches: Iterable,
    *,
    num_steps: int | None = None,
    log_every: int = 50,
    logger=None,
    eval_fn: Callable[[Any], dict] | None = None,
    eval_every: int = 0,
    checkpoint_fn: Callable[[TrainState], None] | None = None,
    checkpoint_every: int = 0,
    tokens_per_batch: int | None = None,
    steps_per_call: int = 1,
    fused_eval: Callable[[dict], dict] | None = None,
    flops_per_token: float | None = None,
    peak_tflops: float | None = None,
    best_fn: Callable | None = None,
    best_metric: str = "eval_loss",
    best_mode: str = "min",
    best_init: float | None = None,
    anomaly_limit: int = 0,
) -> TrainState:
    """Drive the jitted step over a batch iterator, logging scalar metrics.

    The only host↔device traffic per logged step is the scalar metric fetch
    (and even that is amortised over ``log_every`` async-dispatched steps).

    With ``steps_per_call=K`` (the multi-step path, train/multistep.py) each
    iteration is one K-step dispatch: ``num_steps``/``log_every``/
    ``eval_every``/``checkpoint_every`` count CALLS, and throughput metrics
    are scaled by K to stay in optimizer-steps/tokens per second.

    With ``fused_eval`` set (device_step.py's train+eval builders) the step
    signature is ``train_step(state, batch, do_eval)`` and the eval record
    is ``fused_eval(metrics)`` — a task-specific mapper from the step's own
    eval scalars (the LM derives perplexity, the classifier reads accuracy)
    — instead of calling ``eval_fn``: one executable for both cadences,
    zero train/eval program swaps.

    ``best_fn(state, value)`` (e.g. Checkpointer.save_best) fires whenever
    an eval improves ``best_metric`` under ``best_mode`` ("min"/"max") —
    best-checkpoint tracking, independent of the periodic rotation.
    ``best_init`` seeds the best-so-far (a resumed run passes the saved
    best's value so it can never overwrite a better checkpoint with a
    worse one).

    ``anomaly_limit=K`` (off at 0) aborts with
    :class:`AnomalousTrainingError` after K CONSECUTIVE anomalous
    (non-finite, update-skipped) steps — the supervisor restarts from
    checkpoint with the dedicated exit code. Enabling it fetches the
    per-step ``anomalous`` scalar, which adds one host sync per loop
    iteration (the same cost a per-step loss fetch would have): leave it 0
    on dispatch-bound runs that don't need the watchdog. With
    ``steps_per_call=K'`` the fetched value is the window COUNT; a fully
    anomalous window extends the consecutive run, a partially anomalous
    one resets it (it contained at least one finite step).
    """
    window_start = time.perf_counter()
    # telemetry (obs/): step-time/tokens-per-sec recorded at the log
    # cadence from the SAME window timings the JSONL records use (no
    # extra host sync); anomalous steps counted wherever the scalar is
    # already fetched. MetricsLogger.log_registry snapshots these.
    _m_step = obs.REGISTRY.histogram(
        "train_step_seconds", "mean optimizer-step wall time per log window",
        buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 300.0))
    _m_tps = obs.REGISTRY.gauge(
        "train_tokens_per_sec", "training throughput at the last log point")
    _m_steps = obs.REGISTRY.counter(
        "train_steps_total", "optimizer steps driven (log-window granular)")
    _m_anomalous = obs.REGISTRY.counter(
        "train_anomalous_steps_total",
        "non-finite steps whose update was skipped")
    # bptt-mode observability (ops/parallel_scan.py): traces happen on the
    # first dispatch inside this loop, so the fallback delta across the
    # loop captures this run's resolutions. Surfaced in metrics_snapshot
    # (cli.py adds the requested mode string) so a supervised restart can
    # detect a bptt-mode flip between resume legs.
    _m_bptt_fb = obs.REGISTRY.counter(
        "train_bptt_assoc_fallbacks_total",
        "auto bptt resolutions that fell back to the sequential backward")
    _m_bptt_tr = obs.REGISTRY.counter(
        "train_bptt_assoc_traces_total",
        "scans traced with the associative-scan backward")
    _m_donated = obs.REGISTRY.gauge(
        "train_state_donated",
        "1 when the first dispatch consumed the state handed to it")
    _bptt0 = _pscan.assoc_stats()
    if num_steps is not None and num_steps <= 0:
        return state  # eval-only budget: never pull a batch from the feed
    try:
        state = _run_train_loop(
            state, train_step, batches, num_steps=num_steps,
            log_every=log_every, logger=logger, eval_fn=eval_fn,
            eval_every=eval_every, checkpoint_fn=checkpoint_fn,
            checkpoint_every=checkpoint_every,
            tokens_per_batch=tokens_per_batch, steps_per_call=steps_per_call,
            fused_eval=fused_eval, flops_per_token=flops_per_token,
            peak_tflops=peak_tflops, best_fn=best_fn,
            best_metric=best_metric, best_mode=best_mode, best_init=best_init,
            anomaly_limit=anomaly_limit, window_start=window_start,
            _m_step=_m_step, _m_tps=_m_tps, _m_steps=_m_steps,
            _m_anomalous=_m_anomalous, _m_donated=_m_donated,
        )
    finally:
        # counted on every exit path — an anomaly abort's final
        # metrics_snapshot must still carry the bptt evidence
        _b = _pscan.assoc_stats()
        fb = _b["sequential_fallbacks"] - _bptt0["sequential_fallbacks"]
        tr = _b["assoc_traces"] - _bptt0["assoc_traces"]
        if fb:
            _m_bptt_fb.inc(fb)
        if tr:
            _m_bptt_tr.inc(tr)
    return state


def _fed(batches: Iterable):
    """``batches``, each pull from the feed under a ``train:feed`` span."""
    feed, end = iter(batches), object()
    while True:
        with span("train:feed"):
            batch = next(feed, end)
        if batch is end:
            return
        yield batch


def _run_train_loop(
    state, train_step, batches, *, num_steps, log_every, logger, eval_fn,
    eval_every, checkpoint_fn, checkpoint_every, tokens_per_batch,
    steps_per_call, fused_eval, flops_per_token, peak_tflops, best_fn,
    best_metric, best_mode, best_init, anomaly_limit, window_start,
    _m_step, _m_tps, _m_steps, _m_anomalous, _m_donated,
):
    """The drive loop proper (split from `train_loop` so the bptt trace
    accounting above wraps every exit path in one place)."""
    last_metrics = None
    anomalous_total = 0
    anomalous_consec = 0
    best_val = best_init
    donated = None
    for i, batch in enumerate(_fed(batches)):
        if num_steps is not None and i >= num_steps:
            break
        step = i + 1
        # the first device-resident state handed in (a restored one starts
        # as host arrays): did its dispatch take the buffers?
        handed = None
        if donated is None:
            handed = [x for x in jax.tree.leaves(state)
                      if isinstance(x, jax.Array)]
        with span("train:dispatch", steps=steps_per_call):
            if fused_eval:
                do_eval = bool(eval_every) and step % eval_every == 0
                state, metrics = train_step(state, batch, np.bool_(do_eval))
            else:
                state, metrics = train_step(state, batch)
        if handed:
            donated = any(x.is_deleted() for x in handed)
            _m_donated.set(int(donated))
        last_metrics = metrics
        if anomaly_limit and "anomalous" in metrics:
            with span("train:sync"):
                # sync point (documented)
                bad = int(float(metrics["anomalous"]))
            anomalous_total += bad
            if bad:
                _m_anomalous.inc(bad)
            if bad >= steps_per_call:
                anomalous_consec += bad
            else:
                anomalous_consec = 0
            if anomalous_consec >= anomaly_limit:
                if logger is not None:
                    logger.log({"step": int(state.step),
                                "note": "anomaly abort",
                                "anomalous_steps": anomalous_total,
                                "anomalous_consecutive": anomalous_consec})
                raise AnomalousTrainingError(
                    anomalous_consec, anomalous_total, int(state.step))
        if log_every and step % log_every == 0:
            with span("train:sync") as sync:
                loss = float(metrics["loss"])  # sync point
            with span("train:log"):
                now = sync.end
                dt = now - window_start
                window_start = now
                window_steps = log_every * steps_per_call
                _m_step.observe(dt / window_steps)
                _m_steps.inc(window_steps)
                record = {
                    "step": int(state.step),
                    "loss": loss,
                    "grad_norm": float(metrics["grad_norm"]),
                    "steps_per_sec": log_every * steps_per_call / dt,
                }
                if anomaly_limit:
                    # cumulative (exact: every step was fetched above)
                    if anomalous_total:
                        record["anomalous_steps"] = anomalous_total
                elif "anomalous" in metrics:
                    # watchdog off: report the logged step/window's own count
                    # (no per-step fetch, so no cumulative claim)
                    bad = float(metrics["anomalous"])
                    if bad:
                        record["anomalous"] = bad
                        _m_anomalous.inc(bad)
                if tokens_per_batch:
                    tps = tokens_per_batch * log_every * steps_per_call / dt
                    record["tokens_per_sec"] = tps
                    _m_tps.set(tps)
                    if flops_per_token:
                        # live MFU: achieved model TFLOP/s (train = 3x forward
                        # matmul accounting, utils/flops.py). ``peak_tflops``
                        # is the AGGREGATE peak of every participating chip —
                        # tokens_per_sec is the global rate, so dividing by one
                        # chip's peak would overstate MFU by the device count.
                        record["model_tflops"] = tps * flops_per_token / 1e12
                        if peak_tflops:
                            record["mfu"] = round(
                                record["model_tflops"] / peak_tflops, 4
                            )
                if logger is not None:
                    logger.log(record)
        if eval_every and step % eval_every == 0:
            with span("train:eval"):
                if fused_eval is not None:
                    ev = fused_eval(metrics)
                elif eval_fn is not None:
                    ev = eval_fn(state.params)
                else:
                    ev = None
            if ev is not None and logger is not None:
                logger.log({"step": int(state.step), **ev})
            if best_fn is not None and ev is not None and best_metric in ev:
                v = float(ev[best_metric])
                # NaN must never become (or remain) the best: it would win
                # once (any comparison with None/NaN) and then never be
                # beaten, pinning the best checkpoint to a diverged model
                # forever — a NaN seeded via best_init (legacy file)
                # counts as "no best yet"
                no_best = best_val is None or best_val != best_val
                improved = v == v and (no_best or (
                    v < best_val if best_mode == "min" else v > best_val
                ))
                if improved:
                    best_val = v
                    best_fn(state, v)
                    if logger is not None:
                        logger.log({"step": int(state.step),
                                    "note": f"new best {best_metric}",
                                    best_metric: v})
        if checkpoint_fn is not None and checkpoint_every and step % checkpoint_every == 0:
            with span("train:checkpoint"):
                checkpoint_fn(state)
    if last_metrics is not None:
        jax.block_until_ready(last_metrics["loss"])
    return state
