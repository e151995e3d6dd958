"""Training entrypoint — reference CLI parity (SURVEY.md §2 L5 [D]: "keeps
its CLI ... launches on a TPU pod with no Spark JVM").

The reference's flag surface (hidden units, layers, epochs, learning rate,
partitions, data path — SURVEY.md §1 L5 row) is preserved; ``--num-partitions``
maps to the number of mesh devices on the data axis, the direct successor of
the RDD partition count. Where ``spark-submit main.py --flags`` launched a
JVM driver, ``python main.py --flags`` (or ``python -m
lstm_tensorspark_tpu.cli``) builds a device mesh and jit-compiles the train
step; multi-host pods launch the same script once per host with
``--num-processes/--process-id/--coordinator``.
"""

from __future__ import annotations

import argparse
import sys

import jax
import numpy as np

from .utils.compile_cache import place_compile_cache

# The LM task family (word/char language modelling) — ONE definition for
# task dispatch and every LM-specific CLI gate.
LM_DATASETS = ("ptb_char", "wikitext2", "wikitext103")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="lstm_tensorspark_tpu",
        description="TPU-native LSTM training (LSTM-TensorSpark capabilities, no Spark)",
        epilog="Inference serving is a subcommand with its own flags: "
               "`... serve {--selftest | --http}` — run "
               "`... serve --help` (dispatched before this parser, so "
               "`serve` must be the first argument).",
    )
    # --- reference flag surface (SURVEY.md §1 L5) ---
    p.add_argument("--data-path", type=str, default=None,
                   help="corpus directory; it is an error if the dataset's "
                        "files are not there (without this flag: the "
                        "dataset's smaller synthetic stand-in)")
    p.add_argument("--hidden-units", type=int, default=128)
    p.add_argument("--num-layers", type=int, default=1)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--learning-rate", type=float, default=1.0)
    p.add_argument("--num-partitions", type=int, default=None,
                   help="data-parallel shards (reference: RDD partitions) — defaults to all devices")
    # --- capability extensions ---
    p.add_argument("--dataset", type=str, default="ptb_char",
                   choices=["ptb_char", "wikitext2", "wikitext103", "imdb", "uci_electricity"])
    p.add_argument("--batch-size", type=int, default=32, help="global batch size")
    p.add_argument("--seq-len", type=int, default=None,
                   help="window/context length (defaults: LM 64, imdb 400, uci 168)")
    p.add_argument("--optimizer", type=str, default="sgd",
                   choices=["sgd", "momentum", "adam", "adamw", "rmsprop"])
    p.add_argument("--momentum", type=float, default=0.0)
    p.add_argument("--clip-norm", type=float, default=None)
    p.add_argument("--weight-decay", type=float, default=0.0, help="adamw only")
    p.add_argument("--warmup-steps", type=int, default=0,
                   help="linear LR warmup steps (enables warmup-cosine schedule)")
    p.add_argument("--decay-steps", type=int, default=None,
                   help="cosine decay horizon in steps (enables the schedule)")
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--tie-embeddings", action="store_true")
    p.add_argument("--compute-dtype", type=str, default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--logits-dtype", type=str, default="float32",
                   choices=["float32", "bfloat16"],
                   help="dtype of the materialized [B,T,V] LM logits; "
                        "bfloat16 halves every HBM pass over that array "
                        "(+25%% measured at V=33k) while the logsumexp/NLL "
                        "still runs in f32 over the upcast values — "
                        "opt-in numerics trade, LM tasks only (no effect "
                        "on the chunked-xent path at V>=131072, which "
                        "never materializes the array)")
    p.add_argument("--remat-chunk", type=int, default=None,
                   help="jax.checkpoint chunk size over time (long sequences)")
    p.add_argument("--scan-unroll", type=int, default=1)
    p.add_argument("--bptt-mode", type=str, default="auto",
                   choices=["auto", "assoc", "sequential"],
                   help="backward pass through the recurrence "
                        "(ops/parallel_scan.py): 'assoc' = parallel-scan "
                        "BPTT (associative scan of per-step adjoint "
                        "operators, O(log T) depth), 'sequential' = the "
                        "ordinary reverse scan, 'auto' = assoc only when "
                        "the memory plan fits and T is long enough "
                        "(docs/OPERATIONS.md 'BPTT mode')")
    p.add_argument("--use-pallas", action="store_true",
                   help="fused Pallas recurrence kernel (TPU, B%%8==0; any H — "
                        "padded/tiled internally). Its fused backward saves "
                        "O(T) f32 activations in HBM; above ~4 GB (env "
                        "LSTM_TSP_RESIDUAL_HBM_MB) or with --remat-chunk set "
                        "it switches to the recompute backward instead")
    p.add_argument("--stateful", action="store_true",
                   help="stateful truncated BPTT: carry recurrent state across contiguous windows")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="gradient-accumulation microbatches per optimizer step "
                        "(splits the per-shard batch; activation memory drops "
                        "to one microbatch's worth)")
    p.add_argument("--steps-per-call", type=int, default=1,
                   help="K optimizer steps per host dispatch (lax.scan over K "
                        "staged batches — amortises dispatch for small models; "
                        "log/eval/checkpoint cadences then count K-step calls)")
    p.add_argument("--prefetch", type=int, default=0,
                   help="device-prefetch depth for the input feed (0 = off; "
                        "a background-thread device_put competes with "
                        "dispatch for the host — measure before enabling)")
    p.add_argument("--zero1", action="store_true",
                   help="shard the OPTIMIZER state 1/dp over the data axis "
                        "(ZeRO-1): grads reduce-scattered, each shard "
                        "updates its slice of the raveled params with its "
                        "slice of the moments, all-gather rebuilds params "
                        "— same per-step collective volume as plain DP, "
                        "optimizer memory /dp (Adam: 2x params -> "
                        "2x params/dp). Composes with --steps-per-call. "
                        "Requires a DP mesh; not with --stateful/"
                        "--grad-accum/--device-data/--fused-eval/TP/SP/PP. "
                        "ZeRO-1 checkpoints resume at the SAME "
                        "--num-partitions (the sharded moments bake in "
                        "the shard count)")
    p.add_argument("--device-data", action="store_true",
                   help="stage the dataset in device HBM once and build "
                        "batches on-device (LM: window slices; imdb: row "
                        "gather; uci: series windows) — per-dispatch host "
                        "traffic shrinks to indices; the cached-RDD "
                        "equivalent; dataset must fit HBM")
    p.add_argument("--fused-eval", action="store_true",
                   help="run the eval pass INSIDE the train executable on "
                        "device-resident eval data (every task; composes "
                        "with --device-data or the host-fed feed — only the "
                        "EVAL split must fit HBM — and, for the classifier/"
                        "forecaster, with --tensor-parallel): one program "
                        "for both cadences, so an eval costs zero "
                        "train/eval executable swaps — the swap is "
                        "~3 s/eval on dispatch-expensive backends and "
                        "dominates small-model runs")
    # --- inference / generation (LM tasks) ---
    p.add_argument("--generate-tokens", type=int, default=0,
                   help="after training, sample N continuation tokens from the LM")
    p.add_argument("--prompt", type=str, default=None,
                   help="generation prompt text (defaults to the corpus start)")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None,
                   help="nucleus sampling mass in (0, 1]")
    p.add_argument("--greedy", action="store_true", help="argmax decoding")
    p.add_argument("--num-steps", type=int, default=None,
                   help="total step budget for the job, resume-inclusive "
                        "(overrides epochs). An explicit 0 runs ZERO "
                        "training steps — the eval-only recipe with "
                        "--resume (unset falls back to the epoch count)")
    p.add_argument("--eval-every", type=int, default=0)
    p.add_argument("--eval-batches", type=int, default=None,
                   help="cap each eval pass at N batches (default: the full "
                        "held-out split) — bounds eval cost at large dims")
    p.add_argument("--log-every", type=int, default=50)
    p.add_argument("--log-flops", action="store_true",
                   help="add live model-TFLOP/s and MFU (vs the device's "
                        "bf16 peak, utils/flops.py) to every throughput log "
                        "record — matmul-only accounting, train = 3x "
                        "forward")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--anomaly-limit", type=int, default=0,
                   help="abort with the dedicated anomaly exit code "
                        "(resilience/exit_codes.py) after K CONSECUTIVE "
                        "non-finite (NaN/Inf) steps, so the supervisor "
                        "restarts from checkpoint; the guard itself (skip "
                        "the update, count the step) is always on — this "
                        "only adds the abort watchdog, at the cost of one "
                        "host sync per step while enabled (0 = off)")
    p.add_argument("--faults", type=str, default=None,
                   help="ARM FAULT INJECTION (chaos drills only): a "
                        "schedule like 'crash@50;nan_grads@30x2;"
                        "ckpt_corrupt@40' — see resilience/faults.py for "
                        "the grammar; exported as LSTM_TSP_FAULTS to "
                        "children; one-shot faults record their firing "
                        "under --checkpoint-dir/.faults so supervised "
                        "restarts don't re-fire them")
    p.add_argument("--jsonl", type=str, default=None, help="metrics JSONL path")
    p.add_argument("--checkpoint-dir", type=str, default=None)
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--keep-best", action="store_true",
                   help="additionally track the BEST-eval checkpoint "
                        "(best.msgpack + best.json in --checkpoint-dir; "
                        "multi-process runs write sharded "
                        "best_<step>.proc<k> files + a best.complete "
                        "marker instead), overwritten on each improvement "
                        "of the task's eval metric: LM perplexity / "
                        "classifier accuracy / forecast MSE — outside the "
                        "keep-N rotation; requires --checkpoint-dir and "
                        "--eval-every")
    p.add_argument("--async-checkpoint", action="store_true",
                   help="overlap checkpoint serialization + file IO with "
                        "training: save() blocks only for the device-to-"
                        "host snapshot, the write runs on a background "
                        "thread (single-process runs; multi-process saves "
                        "stay synchronous for their barriers)")
    p.add_argument("--resume", action="store_true", help="resume from latest checkpoint in --checkpoint-dir")
    p.add_argument("--resume-best", action="store_true",
                   help="ONE-TIME REWIND to the best-eval checkpoint "
                        "(--keep-best's best.msgpack) — e.g. to fine-tune "
                        "the best model after overfitting. Deletes step_N "
                        "checkpoints newer than the best and re-saves the "
                        "rewound point, so later --resume runs continue "
                        "THIS lineage; mutually exclusive with --resume "
                        "(the supervisor converts it to --resume on "
                        "relaunch); single-process only")
    p.add_argument("--profile-dir", type=str, default=None,
                   help="jax.profiler trace output dir: the device's "
                        "operations and the program's spans in one file "
                        "(Python tracer off; the chip's clock sits a "
                        "millisecond or two off the host's, a constant per "
                        "trace: docs/OPERATIONS.md says how to take it out)")
    p.add_argument("--trace", type=str, default=None,
                   help="the program's spans as a host-side timeline "
                        "(Chrome trace-event JSON; with the device's "
                        "operations beside them: --profile-dir)")
    p.add_argument("--backend", type=str, default="auto", choices=["auto", "single", "dp"],
                   help="auto: dp when >1 device/partition")
    # --- advanced parallelism (LM task; new capability beyond the reference) ---
    p.add_argument("--tensor-parallel", type=int, default=1,
                   help="'model' mesh axis size: gate/hidden dims sharded (GSPMD)")
    p.add_argument("--seq-parallel", type=int, default=1,
                   help="'seq' mesh axis size: wavefront sequence parallelism")
    p.add_argument("--pipeline-stages", type=int, default=1,
                   help="'pipe' mesh axis size: GPipe pipeline over stacked layers")
    p.add_argument("--microbatches", type=int, default=None,
                   help="wavefront microbatches for --seq-parallel/--pipeline-stages")
    # --- multi-host control plane (SURVEY.md §7 step 4) ---
    p.add_argument("--coordinator", type=str, default=None)
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    return p


def main(argv=None) -> int:
    if argv is None:
        import sys

        argv = sys.argv[1:]
    if argv and argv[0] == "serve":
        return _run_serve(argv[1:])
    if argv and argv[0] == "distill":
        return _run_distill(argv[1:])
    args = build_parser().parse_args(argv)
    place_compile_cache()
    if args.temperature <= 0.0:
        raise SystemExit(f"--temperature must be > 0, got {args.temperature}")
    if args.top_k is not None and args.top_k < 1:
        raise SystemExit(f"--top-k must be >= 1, got {args.top_k}")
    if args.top_p is not None and not 0.0 < args.top_p <= 1.0:
        raise SystemExit(f"--top-p must be in (0, 1], got {args.top_p}")
    if args.eval_batches is not None and args.eval_batches < 1:
        raise SystemExit(f"--eval-batches must be >= 1, got {args.eval_batches}")
    # one shared gate for every task runner: the fused kernel cannot run on
    # a "model"-axis-sharded hidden dim (GSPMD cannot partition pallas_call);
    # it DOES compose with --pipeline-stages AND --seq-parallel (their
    # wavefront bodies are collective-free per chunk; both steps make every
    # mesh axis manual when the kernel is live)
    if args.use_pallas and args.tensor_parallel > 1:
        raise SystemExit("--use-pallas is not supported with --tensor-parallel "
                         "(the GSPMD-sharded hidden dim cannot enter the fused "
                         "kernel)")
    if args.fused_eval and max(args.seq_parallel, args.pipeline_stages) > 1:
        raise SystemExit("--fused-eval is not supported with --seq-parallel/"
                         "--pipeline-stages (a lax.cond around their manual "
                         "wavefront collectives would diverge); it composes "
                         "with --backend single/dp and, for the classifier/"
                         "forecaster, with --tensor-parallel")
    if args.fused_eval and args.tensor_parallel > 1 and args.dataset in (
            LM_DATASETS):
        raise SystemExit("--fused-eval with --tensor-parallel is supported "
                         "for the classifier/forecaster (pure GSPMD jit "
                         "steps); the LM's TP step is a manual {data,seq} "
                         "shard_map where a gated eval branch could diverge "
                         "on the auto-axis collectives")
    if args.fused_eval and not args.eval_every:
        raise SystemExit("--fused-eval needs --eval-every > 0 (it fuses the "
                         "PERIODIC eval pass into the train executable; "
                         "without a cadence it would stage eval data and "
                         "compile the eval branch for nothing)")
    if args.keep_best and not (args.checkpoint_dir and args.eval_every):
        raise SystemExit("--keep-best needs --checkpoint-dir (where "
                         "best.msgpack lives) and --eval-every > 0 (the "
                         "metric it tracks)")
    # --keep-best composes with multi-process runs since r4: save_best
    # routes through the sharded writer (best_<step>.proc<k> files + a
    # best.complete marker — train/checkpoint.py)
    if args.resume_best and not args.checkpoint_dir:
        raise SystemExit("--resume-best needs --checkpoint-dir (where the "
                         "producing run's best.msgpack lives) — without it "
                         "the run would silently train from random init")
    if args.resume_best and args.resume:
        raise SystemExit("--resume-best and --resume are mutually exclusive "
                         "(rewind vs continue are different intents; the "
                         "supervisor converts --resume-best to --resume on "
                         "relaunch so a crashed fine-tune continues its own "
                         "lineage)")
    # --resume-best composes with multi-process runs since r4: the rewind's
    # fence deletes on process 0 behind barriers (train/checkpoint.py
    # fence_after), restore/re-save use the sharded writer machinery

    from .parallel import distributed_init
    distributed_init(args.coordinator, args.num_processes, args.process_id)

    from .resilience import faults
    # --faults wins (and is exported to children); a supervised drill arms
    # the CHILDREN via the env var instead
    faults.arm_from_flag_or_env(args.faults, state_dir=args.checkpoint_dir)

    from .train.metrics import MetricsLogger

    # context-managed: the JSONL handle closes on EVERY exit path (a
    # SystemExit out of a task runner used to leak it)
    with MetricsLogger(args.jsonl) as logger:
        from .utils import Tracer, set_tracer
        tracer = None
        if args.trace:
            tracer = Tracer()
            set_tracer(tracer)

        from .train.loop import AnomalousTrainingError

        try:
            if args.dataset in LM_DATASETS:
                rc = _run_lm(args, logger)
            elif args.generate_tokens > 0:
                raise SystemExit(
                    "--generate-tokens applies to the LM datasets only "
                    f"(got --dataset {args.dataset})"
                )
            elif args.dataset == "imdb":
                rc = _run_classifier(args, logger)
            else:
                rc = _run_forecaster(args, logger)
        except AnomalousTrainingError as e:
            # dedicated exit code: the supervisor relaunches with --resume
            # and restores the last (clean — updates were skipped) checkpoint
            import sys

            from .resilience.exit_codes import ANOMALY_RC

            print(f"anomaly abort: {e} (exit {ANOMALY_RC})", file=sys.stderr)
            rc = ANOMALY_RC
        finally:
            if tracer is not None:
                set_tracer(None)  # uninstall first: a failed save must not leak it
                try:
                    tracer.save(args.trace)
                except OSError as e:
                    # never mask the run's own outcome with a trace-write error
                    print(f"warning: could not write --trace file: {e}")
        # final registry snapshot into the JSONL: the run's step-time /
        # tokens-per-sec / anomalous-step telemetry (obs/), same numbers a
        # live /metrics scrape would show. The bptt context rides along
        # (requested mode string + trace/fallback counts) so a supervised
        # restart can detect a bptt-mode flip between resume legs.
        from .obs import REGISTRY

        extra = {}
        if getattr(args, "bptt_mode", None):
            from .ops import parallel_scan

            pstats = parallel_scan.assoc_stats()
            extra = {"bptt_mode": args.bptt_mode,
                     "bptt_assoc_traces": pstats["assoc_traces"],
                     "bptt_sequential_fallbacks":
                         pstats["sequential_fallbacks"]}
        if args.use_pallas:
            from .ops.scan import traced_paths

            # what the traces actually took, next to the start record's
            # prediction (`recurrence`) — the two must agree
            extra["recurrence_traced"] = traced_paths()
        logger.log_registry(REGISTRY, extra=extra or None)
    return rc


def make_cli_optimizer(args, *, clip: bool = True):
    """The one optimizer constructor for every task runner — full flag
    surface (optimizer family, momentum, clipping, weight decay, warmup/
    cosine schedule). ``clip=False`` builds the chain WITHOUT the
    global-norm clip stage — required by the ZeRO-1 step, which clips
    from the psum'd global norm itself (parallel/zero.py)."""
    from .train import make_optimizer

    return make_optimizer(
        args.optimizer, args.learning_rate,
        momentum=args.momentum,
        clip_norm=args.clip_norm if clip else None,
        weight_decay=getattr(args, "weight_decay", 0.0),
        warmup_steps=getattr(args, "warmup_steps", 0),
        decay_steps=getattr(args, "decay_steps", None),
    )


def _select_backend(args):
    """Resolve (mesh or None, shards). None mesh → single-chip path.

    ``--backend dp`` is honored even with one device/partition (a 1-wide
    shard_map — useful to validate DP semantics anywhere); ``auto`` picks
    dp only when more than one shard is in play."""
    n_devices = jax.device_count()
    shards = args.num_partitions or n_devices
    if args.backend == "single" or (args.backend == "auto" and shards <= 1):
        return None, 1
    if shards > n_devices:
        raise SystemExit(
            f"--num-partitions {shards} exceeds {n_devices} available devices"
        )
    return _build_mesh(dp=shards,
                       devices=np.asarray(jax.devices()[:shards])), shards


def _build_mesh(**kw):
    """Slice-aware mesh construction: order devices DCN-slowest
    (make_hybrid_mesh — a no-op layout on one slice/process) so data-axis
    psums decompose into ICI + one DCN phase and model/seq/pipe
    collectives never cross slices. Falls back to the plain ordering ONLY
    when a truncated device list leaves unequal domains (pathological but
    previously legal — e.g. 6 partitions over 2 hosts of 4); a model
    block that would straddle DCN stays the hard error mesh.py makes it."""
    from .parallel import make_hybrid_mesh, make_mesh
    try:
        return make_hybrid_mesh(**kw)
    except ValueError as e:
        if "unequal" not in str(e):
            raise
        return make_mesh(**kw)


def _setup_training(
    args,
    logger,
    *,
    loss_fn,
    params,
    optimizer,
    rng,
    stateful: bool = False,
    carries0=None,
):
    """Shared orchestration for every task runner: backend selection,
    divisibility check, checkpoint wiring (restore BEFORE device placement),
    replication onto the mesh, and batch-stream sharding.

    Returns (state, train_step, mesh, shards, wrap_stream, checkpoint_fn).
    """
    from .data import prefetch_to_device, stacked_batches
    from .parallel import make_dp_train_step, shard_batch
    from .parallel.data_parallel import replicate
    from .train import (
        make_dp_multi_train_step,
        make_multi_train_step,
        make_train_step,
    )
    from .train.loop import init_train_state
    from .obs import REGISTRY
    from .train.sharded_update import place_dp_state, sharded_share

    mesh, shards = _select_backend(args)
    sharded_share_gauge = REGISTRY.gauge(
        "dp_update_sharded_share",
        "percent of parameter bytes whose DP update is sharded over the "
        "data axis (reduce-scatter, update 1/dp, all-gather)")
    sharded_share_gauge.set(0.0)
    if args.batch_size % max(shards, 1) != 0:
        raise SystemExit(
            f"--batch-size {args.batch_size} not divisible by {shards} partitions"
        )
    k = getattr(args, "steps_per_call", 1)
    k = 1 if k is None else k
    if k < 1:
        raise SystemExit(f"--steps-per-call must be >= 1, got {k}")
    accum = getattr(args, "grad_accum", 1) or 1
    if accum < 1:
        raise SystemExit(f"--grad-accum must be >= 1, got {accum}")
    if accum > 1:
        if stateful:
            raise SystemExit("--grad-accum is not supported with --stateful "
                             "(recurrent carries do not microbatch)")
        per_shard = args.batch_size // max(shards, 1)
        if per_shard % accum != 0:
            raise SystemExit(
                f"per-shard batch {per_shard} not divisible by --grad-accum {accum}"
            )
    # write the normalized values back so later branches (e.g. --device-data)
    # reuse THIS validation instead of re-deriving their own
    args.steps_per_call = k
    args.grad_accum = accum

    zero1 = bool(getattr(args, "zero1", False))
    if zero1:
        for bad, why in (
            (mesh is None, "requires a DP mesh (--num-partitions > 1 or "
                           "--backend dp)"),
            (accum > 1, "not with --grad-accum"),
            (stateful, "not with --stateful"),
            (getattr(args, "device_data", False), "not with --device-data"),
            (getattr(args, "fused_eval", False), "not with --fused-eval"),
        ):
            if bad:
                raise SystemExit(f"--zero1: {why}")
        # The ZeRO-1 step clips from the psum'd GLOBAL norm itself; the
        # optax chain must not contain its own (per-slice) clip stage.
        # Rebuilding from args is safe because every task runner's
        # ``optimizer`` comes 1:1 from make_cli_optimizer(args) — if a
        # caller ever passes a custom chain, strip its clip stage there
        # and thread it through instead of relying on this rebuild.
        optimizer = make_cli_optimizer(args, clip=False)

    state = init_train_state(params, optimizer, rng, carries=carries0)
    if zero1:
        from .parallel.zero import make_zero1_opt_init

        # sharded moments from the start — also the checkpoint template,
        # so restore reshards onto exactly these leaves
        state = state._replace(
            opt_state=make_zero1_opt_init(optimizer, mesh)(state.params))

    restored, checkpoint_fn = _wire_checkpoint(args, logger, lambda: state)
    if restored is not None:
        state = restored

    depth = getattr(args, "prefetch", 0) or 0

    if mesh is None:
        if k > 1:
            train_step = make_multi_train_step(
                loss_fn, optimizer, stateful=stateful, grad_accum=accum
            )
        else:
            train_step = make_train_step(
                loss_fn, optimizer, stateful=stateful, grad_accum=accum
            )

        def wrap_stream(it, always_stack=False):
            # always_stack: the fused host-fed train+eval step is a K-step
            # (multistep) program even at K=1, so its feed needs the
            # leading axis regardless of --steps-per-call
            if k > 1 or always_stack:
                it = stacked_batches(it, k)
            if depth > 0:
                it = prefetch_to_device(it, depth)
            return it

    else:
        if zero1:
            from .parallel.zero import make_zero1_train_step

            train_step = make_zero1_train_step(
                loss_fn, optimizer, mesh, clip_norm=args.clip_norm,
                steps_per_call=k,
            )
        elif k > 1:
            train_step = make_dp_multi_train_step(
                loss_fn, optimizer, mesh, stateful=stateful, grad_accum=accum
            )
        else:
            train_step = make_dp_train_step(
                loss_fn, optimizer, mesh, stateful=stateful, grad_accum=accum
            )
        # EVERY leaf gets the placement the step hands back, the step
        # counter and the rng included: left on the host they make the
        # second dispatch a second program (a full recompile of the train
        # step — 26 s at config 5 over four chips)
        if zero1:
            # the moments are already sharded P("data") — placing them
            # again would gather them back onto every shard
            state = state._replace(
                step=replicate(state.step, mesh),
                rng=replicate(state.rng, mesh),
                params=replicate(state.params, mesh),
            )
        else:
            # a large leaf and its moments live sharded over the data
            # axis (train/sharded_update.py), everything else replicated
            state = place_dp_state(state, mesh, stateful=stateful)
            sharded_share_gauge.set(sharded_share(state.params, shards))

        from jax.sharding import NamedSharding, PartitionSpec as P

        def wrap_stream(it, always_stack=False):
            stacked = k > 1 or always_stack
            dim = 1 if stacked else 0
            if stacked:
                it = stacked_batches(it, k)
            if depth > 0:
                sharding = NamedSharding(mesh, P(*([None] * dim), "data"))
                return prefetch_to_device(it, depth, sharding=sharding)
            return (shard_batch(b, mesh, dim=dim) for b in it)

    return state, train_step, mesh, shards, wrap_stream, checkpoint_fn


def _setup_tp_training(args, logger, *, loss_fn, params, optimizer, rng,
                       specs_fn, hidden: int, metric_fn=None,
                       metric_keys=()):
    """Tensor-parallel (GSPMD dp×tp) setup for the classifier/forecaster
    tasks — the compiler-first recipe: annotate param shardings, let XLA
    insert the collectives. Returns the same tuple as _setup_training.

    With ``metric_fn`` set (fused eval), the returned train_step has the
    fused signature ``(state, batch, eval_batches, do_eval)`` — built ONCE
    here, not rebuilt by the task runner.
    """
    from .parallel.tensor_parallel import make_tp_train_step, place_params
    from .train.loop import init_train_state

    tp = args.tensor_parallel
    if getattr(args, "steps_per_call", 1) and args.steps_per_call > 1:
        raise SystemExit("--steps-per-call is not supported with --tensor-parallel")
    if getattr(args, "grad_accum", 1) and args.grad_accum > 1:
        raise SystemExit("--grad-accum is not supported with --tensor-parallel")
    if getattr(args, "device_data", False):
        raise SystemExit("--device-data is not supported with --tensor-parallel")
    if getattr(args, "prefetch", 0):
        raise SystemExit("--prefetch is not supported with --tensor-parallel")
    if hidden % tp != 0:
        raise SystemExit(f"--hidden-units {hidden} not divisible by "
                         f"--tensor-parallel {tp}")
    args.steps_per_call = 1
    args.grad_accum = 1
    n = jax.device_count()
    dp = args.num_partitions or max(n // tp, 1)
    if dp * tp > n:
        raise SystemExit(f"mesh dp*tp={dp * tp} exceeds {n} devices")
    if args.batch_size % dp != 0:
        raise SystemExit(f"--batch-size {args.batch_size} not divisible by dp={dp}")
    mesh = _build_mesh(dp=dp, tp=tp,
                       devices=np.asarray(jax.devices()[: dp * tp]))

    state = init_train_state(params, optimizer, rng)
    restored, checkpoint_fn = _wire_checkpoint(args, logger, lambda: state)
    if restored is not None:
        state = restored
    specs = specs_fn(params)
    # place params with their TP shardings; opt_state (possibly restored —
    # re-initializing would lose momenta) is unconstrained in the step's
    # in_shardings, so jit reshards it to match the params on first call
    state = state._replace(params=place_params(state.params, specs, mesh))

    opt_specs = None
    if getattr(args, "zero1", False):
        # GSPMD ZeRO-1 (parallel/zero.py): moment leaves shard over the
        # data axis too; placing the (fresh or restored) state here means
        # no device ever materializes a replicated copy of the moments
        from .parallel.zero import zero1_tp_opt_specs

        opt_specs = zero1_tp_opt_specs(optimizer, params, specs, mesh)
        state = state._replace(
            opt_state=place_params(state.opt_state, opt_specs, mesh))

    train_step = make_tp_train_step(
        loss_fn, optimizer, mesh, params, param_specs=specs,
        opt_state_specs=opt_specs,
        metric_fn=metric_fn, metric_keys=metric_keys,
    )
    # jit's in_shardings place each host batch; the stream passes through
    return state, train_step, mesh, dp, (lambda it: it), checkpoint_fn


def _wire_checkpoint(args, logger, template_fn):
    """Shared checkpoint/resume wiring. ``template_fn()`` produces the
    restore template lazily — only called when a checkpoint actually exists,
    so fresh --resume runs on sharded state skip the host gather.

    Returns (restored_state_or_None, checkpoint_fn_or_None)."""
    if not args.checkpoint_dir:
        return None, None
    from .train.checkpoint import Checkpointer

    ckpt = Checkpointer(args.checkpoint_dir,
                        async_save=getattr(args, "async_checkpoint", False))
    restored = None
    if getattr(args, "resume_best", False):
        meta = ckpt.best_meta()
        if meta is None:
            raise SystemExit("--resume-best: no best checkpoint in "
                             f"{args.checkpoint_dir} (was --keep-best on "
                             "in the producing run?)")
        restored = ckpt.restore_best(template_fn())
        if restored is None:
            # restore_best quarantines a corrupt best and reports None
            # (train/checkpoint.py): abort BEFORE the fence below, which
            # would destroy the run's valid newer step checkpoints
            raise SystemExit("--resume-best: the best checkpoint in "
                             f"{args.checkpoint_dir} is corrupt (now "
                             "quarantined); no rewind performed")
        # the rewind is a commitment: fence the abandoned lineage (its
        # later step_N checkpoints must not win a future restore_latest)
        # and make the rewound point itself durable as a step checkpoint —
        # a crash before the fine-tune's first own save then resumes HERE,
        # not from random init
        ckpt.fence_after(meta["step"])
        ckpt.save(restored)
        logger.log({"note": f"resumed from BEST checkpoint at step "
                            f"{int(restored.step)}", **meta})
    elif args.resume and ckpt.has_checkpoint():
        restored = ckpt.restore_latest(template_fn())
        if restored is not None:
            logger.log({"note": f"resumed at step {int(restored.step)}"})
        else:
            # checkpoints EXISTED but every one failed verification and
            # was quarantined (train/checkpoint.py): silently training
            # from random init would discard the run's progress without
            # anyone noticing — abort loudly instead (an empty dir, by
            # contrast, is a legitimate fresh start under --resume: the
            # supervisor injects the flag before the first save exists)
            raise SystemExit(
                f"--resume: every checkpoint in {args.checkpoint_dir} "
                "failed verification (now quarantined); refusing to "
                "silently restart from step 0 — inspect the "
                "*.quarantined files")
    elif args.resume and ckpt.has_quarantined():
        # the refusal must PERSIST across a supervisor relaunch: after the
        # quarantine above, has_checkpoint() is False on the next attempt,
        # and without this gate the relaunch would fresh-start from step 0
        # — exactly the silent outcome the abort exists to prevent
        raise SystemExit(
            f"--resume: {args.checkpoint_dir} holds no valid checkpoint "
            "but contains *.quarantined files (a previous attempt found "
            "them corrupt); refusing to silently restart from step 0 — "
            "inspect or clear the quarantined files first")

    def checkpoint_fn(state):
        return ckpt.save(state)

    # EXPLICIT finalizer contract (not attribute-sniffing a bound method):
    # _make_logged_loop calls .finalize after the loop so the last async
    # write is durable before the process reads checkpoints or exits, and
    # a failed final write fails the run. Anyone wrapping checkpoint_fn
    # must carry the attributes forward (.save_best serves --keep-best).
    checkpoint_fn.finalize = ckpt.wait
    checkpoint_fn.save_best = ckpt.save_best
    checkpoint_fn.best_meta = ckpt.best_meta
    return restored, checkpoint_fn


def recurrence_note(args, cfg, shards: int, seq_len: int, d_ins, *,
                    has_mask: bool = False, bidir: bool = False) -> str:
    """The ``recurrence`` field of a ``start`` record: which recurrence
    each LSTM layer of ``cfg`` will run at this run's per-device batch
    (`ops.scan.recurrence_path` — the dispatch's own decision), one note
    per distinct layer input width in ``d_ins``. On a TPU an explicit
    --use-pallas that NO layer can honour is an error naming the shapes,
    never a silent `lax.scan`; off-TPU the scan path stays, with the
    note saying why."""
    from .ops.scan import recurrence_path

    batch = args.batch_size // max(shards, 1) // (args.grad_accum or 1)
    paths = [
        recurrence_path(
            batch, seq_len, d, cfg.hidden_size, use_pallas=cfg.use_pallas,
            compute_dtype=cfg.cdtype, has_mask=has_mask,
            remat_chunk=cfg.remat_chunk, bptt=cfg.bptt, bidir=bidir)
        for d in dict.fromkeys(d_ins)
    ]
    if (cfg.use_pallas and jax.default_backend() == "tpu"
            and all(path == "scan" for path, _ in paths)):
        raise SystemExit(
            f"--use-pallas: no layer can run a fused kernel at per-device "
            f"batch B={batch}, H={cfg.hidden_size}: {paths[0][1]}. Lower "
            "--batch-size, or drop --use-pallas to train on lax.scan.")
    return "; ".join(dict.fromkeys(note for _, note in paths))


def _device_ids(x) -> list[int] | None:
    return (sorted(d.id for d in x.devices())
            if isinstance(x, jax.Array) else None)


def _mfu_logging(args, fwd_flops_per_token, mesh, logger):
    """(flops_per_token, peak_tflops) for train_loop's live-MFU records, or
    (None, None) without --log-flops. THE one place the accounting policy
    lives: train = 3x forward (utils/flops.py), and the peak aggregates
    every chip in the mesh — throughput records are global rates, so
    per-chip MFU must divide by the global peak. The peak comes from the
    device_kind table; on a device that is not in it the records carry
    model_tflops and NO mfu (peak None), and a note says why."""
    if not getattr(args, "log_flops", False):
        return None, None
    from .utils.flops import PEAK_BF16_TFLOPS, TRAIN_FLOPS_MULTIPLIER

    devices = mesh.devices.flat if mesh is not None else jax.devices()[:1]
    kind = devices[0].device_kind
    peak = PEAK_BF16_TFLOPS.get(kind)
    if peak is None:
        logger.log({"note": f"--log-flops: no bf16 peak is recorded for "
                            f"device_kind {kind!r} (utils/flops.py "
                            "PEAK_BF16_TFLOPS) — reporting model_tflops "
                            "without mfu"})
    else:
        peak *= mesh.size if mesh is not None else 1
    return TRAIN_FLOPS_MULTIPLIER * fwd_flops_per_token, peak


def _make_logged_loop(args, state, train_step, batches, steps_per_epoch, logger,
                      eval_fn=None, checkpoint_fn=None, tokens_per_batch=None,
                      fused_eval=None, flops_per_token=None, peak_tflops=None,
                      best_metric="eval_loss", best_mode="min"):
    from .train.loop import train_loop

    best_fn, best_init = None, None
    if getattr(args, "keep_best", False) and checkpoint_fn is not None:
        best_fn = getattr(checkpoint_fn, "save_best", None)
        # seed best-so-far from a previously saved best (resume/restart
        # must never overwrite a better checkpoint with a worse one)
        meta_fn = getattr(checkpoint_fn, "best_meta", None)
        if best_fn is not None and meta_fn is not None:
            meta = meta_fn()
            if meta is not None:
                best_init = meta["value"]

    # explicit `--num-steps 0` means ZERO training steps (the eval-only
    # recipe: resume a checkpoint, skip straight to the final eval) — only
    # an UNSET budget falls back to the epoch count
    total = (args.num_steps if args.num_steps is not None
             else args.epochs * steps_per_epoch)
    # --resume restores state.step; train only the REMAINING budget
    total = max(total - int(state.step), 0)
    k = getattr(args, "steps_per_call", 1)
    k = 1 if k is None or k < 1 else k
    if k > 1:
        # each loop iteration is one K-step dispatch; round up so the step
        # budget is never undershot
        total = -(-total // k)
    from .resilience import faults
    plane = faults.active()
    if plane is not None:
        # chaos drills: crash/data_error faults fire from the batch feed,
        # windowed in GLOBAL step coordinates (resume-stable) — one wrap
        # point covers every task runner and feed kind
        batches = plane.wrap_batches(
            batches, start_step=int(state.step), steps_per_call=k
        )
    if args.profile_dir:
        # the program's own spans (utils/tracing.py) say what the host
        # does; the Python tracer would hook every call of every thread
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(args.profile_dir, profiler_options=options)
    try:
        state = train_loop(
            state,
            train_step,
            batches,
            num_steps=total,
            log_every=args.log_every,
            logger=logger,
            eval_fn=eval_fn,
            eval_every=args.eval_every,
            checkpoint_fn=checkpoint_fn,
            checkpoint_every=args.checkpoint_every,
            tokens_per_batch=tokens_per_batch,
            steps_per_call=k,
            fused_eval=fused_eval,
            flops_per_token=flops_per_token,
            peak_tflops=peak_tflops,
            best_fn=best_fn,
            best_metric=best_metric,
            best_mode=best_mode,
            best_init=best_init,
            anomaly_limit=getattr(args, "anomaly_limit", 0) or 0,
        )
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
        # finalize async checkpointing (the _wire_checkpoint contract): the
        # LAST write must be durable before this process reads checkpoints
        # (same-process --resume) or exits, and a failed final write must
        # fail the run, not vanish.
        fin = getattr(checkpoint_fn, "finalize", None)
        if fin is not None:
            fin()
    return state


def _run_lm(args, logger) -> int:
    from .data import get_dataset, lm_batch_stream, lm_epoch_batches
    from .models import LMConfig, init_lm, lm_loss
    from .train import make_optimizer, make_eval_step
    from .train.loop import evaluate
    from .parallel import make_dp_eval_step, shard_batch
    from .parallel.data_parallel import replicate

    from .utils import span

    seq_len = args.seq_len or 64
    with span("load_dataset", dataset=args.dataset):
        data = get_dataset(args.dataset, args.data_path)
    if data["synthetic"]:
        logger.log({"note": f"dataset {args.dataset}: no --data-path, using "
                            "the synthetic stand-in (vocabulary "
                            f"{len(data['vocab'])})"})
    vocab = data["vocab"]
    cfg = LMConfig(
        vocab_size=len(vocab),
        hidden_size=args.hidden_units,
        num_layers=args.num_layers,
        dropout=args.dropout,
        tie_embeddings=args.tie_embeddings,
        compute_dtype=args.compute_dtype,
        remat_chunk=args.remat_chunk,
        scan_unroll=args.scan_unroll,
        use_pallas=args.use_pallas,
        logits_dtype=args.logits_dtype,
        bptt=args.bptt_mode,
    )

    if max(args.tensor_parallel, args.seq_parallel, args.pipeline_stages) > 1:
        return _run_lm_advanced(args, logger, cfg, data, seq_len)

    stateful = args.stateful

    if stateful:

        def loss_fn(params, batch, dropout_rng, carries):
            return lm_loss(
                params, batch, cfg, carries=carries,
                dropout_rng=dropout_rng,
                deterministic=dropout_rng is None or cfg.dropout == 0.0,
            )

    else:

        def loss_fn(params, batch, dropout_rng):
            return lm_loss(
                params, batch, cfg,
                dropout_rng=dropout_rng,
                deterministic=dropout_rng is None or cfg.dropout == 0.0,
            )

    key = jax.random.PRNGKey(args.seed)
    kparams, krng = jax.random.split(key)
    with span("setup", hidden=cfg.hidden_size, layers=cfg.num_layers):
        params = init_lm(kparams, cfg)
        optimizer = make_cli_optimizer(args)
        from .models.lstm_lm import init_carries
        carries0 = init_carries(cfg, args.batch_size) if stateful else None

        state, train_step, mesh, shards, wrap_stream, checkpoint_fn = _setup_training(
            args, logger,
            loss_fn=loss_fn, params=params, optimizer=optimizer, rng=krng,
            stateful=stateful, carries0=carries0,
        )

    train_tokens, valid_tokens = data["train"], data["valid"]
    steps_per_epoch = max((len(train_tokens) - 1) // (args.batch_size * seq_len), 1)
    # The valid split can be smaller than one training-size window; evaluate
    # with the largest batch that fits (multiple of the shard count).
    eval_bs = min(args.batch_size, max((len(valid_tokens) - 1) // seq_len, 0))
    eval_bs -= eval_bs % max(shards, 1)

    fused_eval = bool(args.fused_eval)
    if fused_eval and eval_bs <= 0:
        logger.log({"note": "fused-eval: valid split smaller than one "
                            "window; falling back to host-driven eval"})
        fused_eval = False
    # data-exact resume: fast-forward every stream to the restored step so a
    # resumed run sees exactly the windows the uninterrupted run would
    start_step = int(state.step)
    if args.device_data:
        if args.prefetch:
            raise SystemExit("--device-data has no host feed; drop --prefetch")
        from .data import stage_lm_data, window_index_stream
        from .train import (
            make_device_dp_lm_train_step,
            make_device_lm_train_step,
        )

        # values below were normalized+validated by _setup_training
        k = args.steps_per_call
        ddata = stage_lm_data(train_tokens, args.batch_size, seq_len, mesh=mesh)
        edata = (stage_lm_data(valid_tokens, eval_bs, seq_len, mesh=mesh)
                 if fused_eval else None)
        if mesh is None:
            dstep = make_device_lm_train_step(
                loss_fn, optimizer, ddata, eval_data=edata,
                eval_windows=args.eval_batches, steps_per_call=k,
                stateful=stateful, grad_accum=args.grad_accum,
            )
        else:
            dstep = make_device_dp_lm_train_step(
                loss_fn, optimizer, ddata, mesh, eval_data=edata,
                eval_windows=args.eval_batches, steps_per_call=k,
                stateful=stateful, grad_accum=args.grad_accum,
            )
        if fused_eval:
            ev_carries0 = init_carries(cfg, eval_bs) if stateful else None
            if mesh is not None and stateful:
                ev_carries0 = shard_batch(ev_carries0, mesh)
            train_step = lambda state, w0, do_eval: dstep(  # noqa: E731
                state, ddata.arrays, w0, edata.arrays, do_eval, ev_carries0
            )
        else:
            train_step = lambda state, w0: dstep(state, ddata.arrays, w0)  # noqa: E731
        batches = window_index_stream(ddata, k, start_step=start_step)
    else:
        stream = lm_batch_stream(
            train_tokens, args.batch_size, seq_len, start_step=start_step
        )
        if fused_eval:
            # host-fed train feed + fused in-executable eval: only the VALID
            # split must fit HBM (the case where the train set exceeds it)
            from .data import stage_lm_data
            from .train import make_dp_multi_train_step, make_multi_train_step

            edata = stage_lm_data(valid_tokens, eval_bs, seq_len, mesh=mesh)
            ev_carries0 = init_carries(cfg, eval_bs) if stateful else None
            if mesh is not None and stateful:
                ev_carries0 = shard_batch(ev_carries0, mesh)
            if mesh is None:
                mstep = make_multi_train_step(
                    loss_fn, optimizer, eval_data=edata,
                    eval_windows=args.eval_batches,
                    stateful=stateful, grad_accum=args.grad_accum,
                )
            else:
                mstep = make_dp_multi_train_step(
                    loss_fn, optimizer, mesh, eval_data=edata,
                    eval_windows=args.eval_batches,
                    stateful=stateful, grad_accum=args.grad_accum,
                )
            train_step = lambda state, b, do_eval: mstep(  # noqa: E731
                state, b, edata.arrays, do_eval, ev_carries0
            )
            batches = wrap_stream(stream, always_stack=True)
        else:
            batches = wrap_stream(stream)

    if mesh is None:
        eval_step = make_eval_step(loss_fn, stateful=stateful)
    else:
        eval_step = make_dp_eval_step(loss_fn, mesh, stateful=stateful)

    from .data.batching import cap_batches

    def eval_fn(params):
        if eval_bs <= 0:
            return {"eval_skipped": 1}
        ev = cap_batches(lm_epoch_batches(valid_tokens, eval_bs, seq_len),
                         args.eval_batches)
        ev_carries = init_carries(cfg, eval_bs) if stateful else None
        if mesh is not None:
            ev = (shard_batch(b, mesh) for b in ev)
            if stateful:
                ev_carries = shard_batch(ev_carries, mesh)
            # a large leaf lives sharded between train steps
            # (train/sharded_update.py): gather it once a sweep, not once
            # a batch (a no-op for leaves that are whole already)
            params = replicate(params, mesh)
        return evaluate(eval_step, params, ev, carries=ev_carries)

    logger.log({
        "note": "start", "dataset": args.dataset, "vocab": len(vocab),
        "devices": jax.device_count(), "partitions": shards,
        "steps_per_epoch": steps_per_epoch, "backend": "dp" if mesh is not None else "single",
        "recurrence": recurrence_note(
            args, cfg, shards, seq_len,
            [cfg.embed] + [cfg.hidden_size] * (cfg.num_layers - 1)),
        # where the placed train state lives, read off the array itself
        # (None: still on the host, e.g. just restored — the first step
        # places it)
        "state_on": _device_ids(jax.tree.leaves(state.params)[0]),
    })
    from .train.loop import eval_metrics

    from .utils.flops import lm_fwd_flops_per_token

    flops_per_token, peak = _mfu_logging(
        args,
        lm_fwd_flops_per_token(cfg.vocab_size, cfg.hidden_size,
                               cfg.num_layers, cfg.embed),
        mesh, logger,
    )

    with span("train", steps_per_epoch=steps_per_epoch, backend="dp" if mesh is not None else "single"):
        state = _make_logged_loop(
            args, state, train_step, batches, steps_per_epoch, logger,
            eval_fn=None if fused_eval else (eval_fn if args.eval_every else None),
            checkpoint_fn=checkpoint_fn,
            tokens_per_batch=args.batch_size * seq_len,
            fused_eval=(lambda ms: eval_metrics(float(ms["eval_loss"])))
            if fused_eval else None,
            flops_per_token=flops_per_token,
            peak_tflops=peak,
        )
    with span("eval_final"):
        final = eval_fn(state.params)
    logger.log({"step": int(state.step), **final, "note": "final"})
    if args.generate_tokens > 0:
        with span("generate", tokens=args.generate_tokens):
            _generate_text(args, logger, cfg, data, jax.device_get(state.params))
    return 0


def _generate_text(args, logger, cfg, data, params_host) -> None:
    """Post-training sampling (models/generate.py): encode the prompt, run
    the jitted prefill+decode program, print/log the decoded continuation."""
    from .models import make_generate_fn

    level = "char" if args.dataset == "ptb_char" else "word"
    vocab = data["vocab"]
    if args.prompt:
        prompt_ids = vocab.encode_text(args.prompt, level)
        if prompt_ids.size == 0:
            prompt_ids = np.asarray(data["train"][:8], np.int32)
    else:
        prompt_ids = np.asarray(data["train"][:32], np.int32)
    gen = make_generate_fn(
        cfg,
        max_new_tokens=args.generate_tokens,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        greedy=args.greedy,
    )
    rng = jax.random.PRNGKey(args.seed + 17)
    out = np.asarray(gen(params_host, prompt_ids[None, :], rng))[0]
    sep = "" if level == "char" else " "
    prompt_txt = sep.join(vocab.decode(prompt_ids))
    cont_txt = sep.join(vocab.decode(out[prompt_ids.size:]))
    logger.log({
        "note": "generate", "prompt": prompt_txt, "continuation": cont_txt,
        "temperature": args.temperature, "top_k": args.top_k,
        "top_p": args.top_p, "greedy": bool(args.greedy),
    })
    print(f"--- prompt ---\n{prompt_txt}\n--- continuation ---\n{cont_txt}")


def _run_lm_advanced(args, logger, cfg, data, seq_len) -> int:
    """LM training under tensor/sequence/pipeline parallelism (± DP) on an
    explicit 4-axis mesh — the CLI surface for the strategies beyond the
    reference's data-parallel-only scope (DESIGN.md parallelism table).

    Eval runs SHARDED on the device-resident params (pp/tp/sp eval steps) —
    no host gather; only post-training generation pulls params to host
    (sequential small-batch decode).
    """
    if getattr(args, "zero1", False) and args.pipeline_stages <= 1:
        raise SystemExit(
            "--zero1 with the LM's --tensor-parallel/--seq-parallel steps "
            "is not supported (their update runs inside a manual "
            "{data,seq} shard_map, where the GSPMD weight-update-sharding "
            "form cannot pin the moments). It DOES compose with "
            "--pipeline-stages (stage x data sharded moments) and with "
            "the classifier/forecaster --tensor-parallel runners "
            "(parallel/zero.py).")
    from .data import lm_batch_stream, lm_epoch_batches
    from .models import init_lm
    from .parallel import (
        make_pp_lm_train_step,
        make_sharded_lm_train_step,
        place_pp_lm_params,
        stack_lm_params,
        unstack_lm_params,
    )
    from .parallel.tensor_parallel import place_lm_params
    from .train import make_optimizer
    from .train.loop import evaluate, init_train_state

    tp, sp, pp = args.tensor_parallel, args.seq_parallel, args.pipeline_stages
    if getattr(args, "steps_per_call", 1) > 1:
        raise SystemExit("--steps-per-call is not supported with "
                         "--tensor-parallel/--seq-parallel/--pipeline-stages")
    if getattr(args, "grad_accum", 1) > 1:
        raise SystemExit("--grad-accum is not supported with --tensor-parallel/"
                         "--seq-parallel/--pipeline-stages (use --microbatches "
                         "for the wavefront schedules)")
    if getattr(args, "device_data", False):
        raise SystemExit("--device-data is not supported with --tensor-parallel/"
                         "--seq-parallel/--pipeline-stages (these steps place "
                         "their own shardings)")
    if getattr(args, "prefetch", 0) > 0:
        raise SystemExit("--prefetch is not supported with "
                         "--tensor-parallel/--seq-parallel/--pipeline-stages "
                         "(these steps place their own shardings)")
    if args.stateful:
        raise SystemExit("--stateful is not supported with --tensor-parallel/"
                         "--seq-parallel/--pipeline-stages")
    if pp > 1 and sp > 1:
        raise SystemExit("--pipeline-stages cannot combine with --seq-parallel "
                         "(both schedule the wavefront; tp composes with either)")
    # --use-pallas composes with --seq-parallel since r4: each wavefront
    # chunk runs the fused kernel at the local [b, T/S, D] shard (no
    # collectives inside a chunk; the step's shard_map goes all-manual —
    # parallel/train_step.py). The remaining exclusion is TP, already
    # rejected by the shared gate above (GSPMD cannot partition the kernel).
    if args.microbatches is not None and args.microbatches < 1:
        raise SystemExit(f"--microbatches must be >= 1, got {args.microbatches}")
    n = jax.device_count()
    dp = args.num_partitions or max(n // (tp * sp * pp), 1)
    total = dp * tp * sp * pp
    if total > n:
        raise SystemExit(f"mesh dp*tp*sp*pp={total} exceeds {n} devices")
    if tp > 1 and args.hidden_units % tp != 0:
        raise SystemExit(f"--hidden-units {args.hidden_units} not divisible by "
                         f"--tensor-parallel {tp}")
    if seq_len % max(sp, 1) != 0:
        raise SystemExit(f"--seq-len {seq_len} not divisible by --seq-parallel {sp}")
    mb = args.microbatches if args.microbatches is not None else (pp if pp > 1 else 1)
    if args.batch_size % (dp * mb) != 0:
        raise SystemExit(f"--batch-size {args.batch_size} not divisible by "
                         f"dp*microbatches = {dp}*{mb}")
    mesh = _build_mesh(dp=dp, tp=tp, sp=sp, pp=pp,
                       devices=np.asarray(jax.devices()[:total]))

    optimizer = make_cli_optimizer(args)
    params = init_lm(jax.random.PRNGKey(args.seed), cfg)
    zero1 = bool(getattr(args, "zero1", False)) and pp > 1
    if pp > 1:
        stacked = stack_lm_params(params)
        train_step = make_pp_lm_train_step(
            cfg, optimizer, mesh, stacked, microbatches=mb, tp=tp > 1,
            zero1=zero1,
        )
        placed = place_pp_lm_params(stacked, mesh, tp=tp > 1)
    else:
        train_step = make_sharded_lm_train_step(
            cfg, optimizer, mesh, params, microbatches=mb
        )
        placed = place_lm_params(params, mesh)
    state = init_train_state(placed, optimizer, jax.random.PRNGKey(args.seed + 1))
    if zero1:
        from .parallel.pipeline_parallel import place_pp_zero1_opt_state

        state = state._replace(opt_state=place_pp_zero1_opt_state(
            state.opt_state, optimizer, stacked, mesh, tp=tp > 1))

    restored, checkpoint_fn = _wire_checkpoint(
        args, logger, lambda: jax.device_get(state)
    )
    if restored is not None:
        state = restored

    # Sharded eval on the DEVICE-RESIDENT params — no host gather (the point
    # of PP/TP is that one device need not hold the model); loss/token math
    # runs under the same wavefront as training, deterministic.
    if pp > 1:
        from .parallel.pipeline_parallel import make_pp_lm_eval_step

        eval_step = make_pp_lm_eval_step(
            cfg, mesh, stacked, microbatches=mb, tp=tp > 1
        )
    else:
        from .parallel.train_step import make_sharded_lm_eval_step

        eval_step = make_sharded_lm_eval_step(cfg, mesh, params, microbatches=mb)
    valid_tokens = data["valid"]
    eval_bs = min(args.batch_size, max((len(valid_tokens) - 1) // seq_len, 0))
    # the wavefront divisibility contracts hold for eval batches too
    eval_quantum = dp * mb if pp > 1 else dp
    eval_bs -= eval_bs % max(eval_quantum, 1)

    from .data.batching import cap_batches

    def eval_fn(params_dev):
        if eval_bs <= 0:
            return {"eval_skipped": 1}
        ev = cap_batches(lm_epoch_batches(valid_tokens, eval_bs, seq_len),
                         args.eval_batches)
        return evaluate(eval_step, params_dev, ev)

    train_tokens = data["train"]
    steps_per_epoch = max((len(train_tokens) - 1) // (args.batch_size * seq_len), 1)
    # data-exact resume (same contract as _run_lm's streams)
    batches = lm_batch_stream(train_tokens, args.batch_size, seq_len,
                              start_step=int(state.step))

    logger.log({
        "note": "start", "dataset": args.dataset, "vocab": cfg.vocab_size,
        "devices": n, "mesh": {"dp": dp, "tp": tp, "sp": sp, "pp": pp},
        "microbatches": mb, "steps_per_epoch": steps_per_epoch,
        "backend": "pp" if pp > 1 else "tp/sp",
    })
    from .utils.flops import lm_fwd_flops_per_token

    flops_per_token, peak = _mfu_logging(
        args,
        lm_fwd_flops_per_token(cfg.vocab_size, cfg.hidden_size,
                               cfg.num_layers, cfg.embed),
        mesh, logger,
    )
    state = _make_logged_loop(
        args, state, train_step, batches, steps_per_epoch, logger,
        eval_fn=eval_fn if args.eval_every else None,
        checkpoint_fn=checkpoint_fn,
        tokens_per_batch=args.batch_size * seq_len,
        flops_per_token=flops_per_token,
        peak_tflops=peak,
    )
    final = eval_fn(state.params)
    logger.log({"step": int(state.step), **final, "note": "final"})
    if args.generate_tokens > 0:
        params_host = jax.device_get(state.params)
        if pp > 1:
            params_host = unstack_lm_params(params_host)
        _generate_text(args, logger, cfg, data, params_host)
    return 0


def build_serve_parser() -> argparse.ArgumentParser:
    """``serve`` subcommand: the inference engine's CLI surface (serve/)."""
    from .serve.engine import DECODE_KERNELS

    p = argparse.ArgumentParser(
        prog="lstm_tensorspark_tpu serve",
        description="continuous-batching LM inference (serve/): HTTP "
                    "endpoint, --selftest parity check",
    )
    mode = p.add_mutually_exclusive_group()
    mode.add_argument("--selftest", action="store_true",
                      help="decode a batch of concurrent sessions and "
                           "verify greedy output is token-identical to "
                           "models/generate.py; rc 0 on PASS")
    mode.add_argument("--http", action="store_true",
                      help="run the JSON HTTP endpoint (default mode)")
    # --- model (must match the producing training run) ---
    p.add_argument("--vocab-size", type=int, default=89)
    p.add_argument("--hidden-units", type=int, default=64)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--tie-embeddings", action="store_true")
    p.add_argument("--compute-dtype", type=str, default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   help="restore trained params from a training run's "
                        "checkpoints (the model flags must match it; its "
                        "optimizer does not matter); random init otherwise")
    # --- the decoder family (models/decoder.py, serve/decoder_engine.py) ---
    p.add_argument("--model-file", type=str, default=None,
                   help="serve a DECODER described by this JSON file: the "
                        "published config.json keys, the share this chip "
                        "holds, assumed.weights_seed. Its model_type picks "
                        "the block: deepseek_v2 (latent attention over a "
                        "paged latent cache, group-limited routed and "
                        "shared experts; e.g. benchmark/configs/"
                        "deepseek-v2-ep4.json, sized by --latent-pool-gib) "
                        "or mellum (grouped-query attention over a paged "
                        "K/V cache whose window layers keep a session's "
                        "last pages, renormalised top-k experts; "
                        "benchmark/configs/mellum2-12b-l8.json, sized by "
                        "--kv-pool-gib and --kv-window-gib). The same "
                        "server, router, batcher and session API; the "
                        "LSTM model flags are then unused, and the "
                        "LSTM-only features (--prefix-cache/--prefix-fabric "
                        "on, --tiered-cache on, --session-dir, "
                        "--speculative, --mesh-shards, --replicas > 1, "
                        "--checkpoint-dir, --registry-dir, --autotune on, "
                        "sampled decoding) are REFUSED")
    p.add_argument("--weights-dtype", type=str, default="bfloat16",
                   choices=["bfloat16", "float32"],
                   help="decoder: dtype of the seeded weights and of the "
                        "latent cache (XLA:CPU has no bf16 x bf16 -> f32 "
                        "dot, so CPU rehearsals pass float32)")
    p.add_argument("--interpret-kernels", action="store_true",
                   help="decoder: run its Pallas kernels in interpret mode "
                        "(a CPU rehearsal; without it a host with no TPU "
                        "fails at the first program instead of carrying on "
                        "in the interpreter)")
    p.add_argument("--latent-pool-gib", type=float, default=0.25,
                   help="decoder: device memory of the paged latent cache, "
                        "all layers together (pages are taken as sessions "
                        "grow and freed when they end; nothing is evicted)")
    p.add_argument("--kv-pool-gib", type=float, default=0.25,
                   help="decoder with keys and values per head (model_type "
                        "mellum): device memory of the paged K/V cache, "
                        "both kinds of page together (full-attention "
                        "layers' pages are kept while a session lives; "
                        "window layers' pages are returned as it outgrows "
                        "them)")
    p.add_argument("--kv-window-gib", type=float, default=None,
                   help="the part of --kv-pool-gib given to the window "
                        "layers' pages (a session holds at most 7 of them "
                        "at pages of 256 and a 1,024-token window, "
                        "however long it is); required with such a "
                        "model file: nothing derives it")
    p.add_argument("--page-size", type=int, default=256,
                   help="decoder: tokens per page of the cache")
    p.add_argument("--max-context", type=int, default=4096,
                   help="decoder: the most tokens one session may hold")
    p.add_argument("--prefill-rows", type=int, default=4,
                   help="decoder: rows one prefill dispatch may pack onto "
                        "its flat token axis")
    # --- engine / batcher (docs/OPERATIONS.md "Serving") ---
    p.add_argument("--replicas", type=_positive_int, default=1,
                   help="data-parallel serving replicas (serve/router.py): "
                        "N engine+scheduler replicas behind one admission "
                        "router with session→replica affinity — thread-per-"
                        "replica on CPU, device-per-replica when multiple "
                        "accelerators exist. --num-slots/--max-active are "
                        "PER REPLICA; --queue-size is the global admission "
                        "bound")
    p.add_argument("--num-slots", type=int, default=64,
                   help="state-cache slots (= max resident sessions)")
    p.add_argument("--prefill-buckets", type=str, default="8,16,32,64,128",
                   help="prompt-length pad buckets; the largest is the "
                        "prompt-length admission limit")
    p.add_argument("--batch-buckets", type=str, default="1,2,4,8,16",
                   help="batch-size pad buckets; the largest bounds one "
                        "packed step")
    p.add_argument("--max-active", type=int, default=16,
                   help="concurrent decode sessions (<= --num-slots)")
    p.add_argument("--queue-size", type=int, default=64,
                   help="bounded submit queue; beyond it requests are "
                        "rejected (HTTP 429). This is the PRIORITY-class "
                        "bound; best-effort sheds earlier "
                        "(--best-effort-queue-frac)")
    p.add_argument("--class-weights", type=str, default="4,1",
                   help="weighted-dequeue shares 'priority,best_effort' "
                        "(serve/batcher.py): out of every P+B admissions "
                        "with both classes waiting, P are priority — the "
                        "SLO lever that keeps priority TTFT flat while a "
                        "best-effort burst queues")
    p.add_argument("--best-effort-queue-frac", type=float, default=0.5,
                   help="best-effort requests are 429-shed once the live "
                        "queue reaches this fraction of --queue-size "
                        "(priority keeps the remaining headroom); sheds "
                        "carry Retry-After from the live queue-wait p99")
    p.add_argument("--deadline-priority-s", type=float, default=0,
                   help="default request deadline (seconds) for the "
                        "priority class; expiry is enforced at admission, "
                        "in the queue and at decode-window boundaries, "
                        "producing an honest 'timeout' outcome with "
                        "partial output. 0 = no default (clients can "
                        "still send deadline_s / X-Deadline-S)")
    p.add_argument("--deadline-best-effort-s", type=float, default=0,
                   help="default request deadline (seconds) for the "
                        "best_effort class; 0 = no default")
    p.add_argument("--replica-stale-s", type=float, default=60.0,
                   help="scheduler-heartbeat staleness bound (seconds) "
                        "before a replica counts wedged: excluded from "
                        "fresh routing and /healthz health (previously a "
                        "hardcoded 60 s)")
    p.add_argument("--replica-sweep-s", type=float, default=0,
                   help="periodic replica death-sweep interval (seconds): "
                        "retire dead replicas (requeue/migrate) within "
                        "this bound even on a quiet server with no "
                        "traffic or probes. 0 = piggyback-only (the "
                        "previous behavior: sweeps run on every submit "
                        "and health probe)")
    p.add_argument("--mesh-shards", type=int, default=1,
                   help="tensor-parallel SHARDS per replica: each "
                        "replica's engine shards its params and "
                        "state-cache slots over a mesh_shards-device "
                        "('model',) mesh (GSPMD — parallel/"
                        "tensor_parallel.py specs), so a model one chip "
                        "cannot hold serves behind the router as one "
                        "replica. Replicas get disjoint device groups "
                        "when the host has replicas*shards devices, and "
                        "share one group otherwise. Token-identical to "
                        "a single-device engine (greedy AND sampled). "
                        "On CPU use XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N for "
                        "virtual devices. 1 = off")
    p.add_argument("--remote-replica", action="append", default=[],
                   metavar="URL",
                   help="add a REMOTE replica behind the router: the "
                        "base URL of a peer `cli serve --http` process "
                        "(repeatable). Generate RPCs ride its "
                        "/v1/generate, liveness its /replica/heartbeat, "
                        "session affinity its /replica/has_session — so "
                        "admission becomes a front-of-fleet tier. Share "
                        "one --session-dir across hosts and a killed "
                        "host loses no kept session (continuations fill "
                        "from the shared disk tier on survivors; "
                        "docs/OPERATIONS.md 'Mesh serving')")
    p.add_argument("--remote-timeout-s", type=float, default=120.0,
                   help="client-side wait bound (seconds) for one remote "
                        "generate RPC (--remote-replica): past it the "
                        "front settles the request honestly instead of "
                        "holding the slot forever. 0 = no bound; a "
                        "request deadline always tightens it. Negative "
                        "rejected at construction")
    p.add_argument("--decode-window", type=str, default="auto",
                   help="multi-token decode window: 'auto' (adaptive "
                        "ladder 1/4/8 — large windows in steady-state "
                        "decode, 1 whenever requests are queued), an int "
                        "N (the ladder capped at N, N as top rung), or 1 "
                        "to pin the per-token path (lowest inter-token "
                        "latency; see docs/OPERATIONS.md). Every window "
                        "size is one XLA compile key per batch bucket.")
    p.add_argument("--decode-kernel", type=str, default="auto",
                   choices=DECODE_KERNELS,
                   help="decode-window kernel: 'scan' (the lax.scan "
                        "window), 'pallas' (fused VMEM-resident window "
                        "kernel, ops/pallas_decode.py — interpreter mode "
                        "off-TPU, token-identical but slow there), or "
                        "'auto' (pallas on TPU when the VMEM plan fits, "
                        "scan otherwise). See docs/OPERATIONS.md for when "
                        "to pin 'scan'.")
    p.add_argument("--prefix-cache", type=str, default=None,
                   choices=["on", "off"],
                   help="shared-prompt prefix-state cache: fresh prompts "
                        "resume prefill from the longest cached prefix "
                        "(an LSTM prefix state is ONE (h, c) pair — reuse "
                        "is a slot copy). Greedy output is token-identical "
                        "on or off; 'off' frees the backing slots "
                        "(docs/OPERATIONS.md)")
    p.add_argument("--prefix-stride", type=int, default=8,
                   help="prefix-cache insert granularity (tokens): entries "
                        "live at stride-aligned prompt lengths")
    p.add_argument("--prefix-entries", type=int, default=16,
                   help="max cached prefix entries (each holds one "
                        "state-cache slot; LRU beyond this)")
    p.add_argument("--prefix-fabric", type=str, default="off",
                   choices=["on", "off"],
                   help="prefix-state FABRIC (serve/prefix_trie.py): "
                        "replaces the exact-match prefix cache with a "
                        "radix trie over token sequences — lookups match "
                        "the LONGEST shared prefix (tenant preambles, "
                        "few-shot templates), cold nodes spill to the "
                        "host tier under --prefix-host-mb, and hot "
                        "inserts propagate to --remote-replica peers "
                        "(idempotent by token hash). Supersedes "
                        "--prefix-cache when on; greedy output stays "
                        "token-identical (docs/OPERATIONS.md)")
    p.add_argument("--prefix-nodes", type=int, default=64,
                   help="max stateful trie nodes per replica with "
                        "--prefix-fabric on (device-resident ones each "
                        "hold a state-cache slot; eviction is leaf-first "
                        "LRU over zero-ref nodes)")
    p.add_argument("--prefix-host-mb", type=float, default=64.0,
                   help="host-RAM bound (MiB) for SPILLED fabric nodes "
                        "(a spilled node is one (h, c) pair per layer "
                        "held by the tiers); the coldest zero-ref "
                        "spilled nodes are dropped past this")
    p.add_argument("--tiered-cache", type=str, default=None,
                   choices=["on", "off"],
                   help="tiered session-state cache (serve/state_cache.py "
                        "SessionTiers): LRU-evicted session states spill "
                        "ASYNC to host RAM (tier 1) with a durable disk "
                        "tier below (--session-dir); continuations of "
                        "spilled sessions fill back for one state copy "
                        "instead of failing 'expired' — the long-tail "
                        "multi-tenant lever (thousands of mostly-idle "
                        "sessions over a few device slots). 'off' keeps "
                        "the fixed-slot behavior (evicted = expired)")
    p.add_argument("--host-tier-entries", type=int, default=256,
                   help="max spilled session states held in host RAM "
                        "(each is one tiny (h, c) pair per layer); "
                        "overflow cascades to --session-dir or is "
                        "dropped honestly")
    p.add_argument("--session-dir", type=str, default=None,
                   help="disk tier + serve-session checkpoints: kept "
                        "sessions are write-behind checkpointed here at "
                        "each request boundary (sha256-verified atomic "
                        "files), so a supervised kill/restart resumes "
                        "them token-identically; also the overflow tier "
                        "below --host-tier-entries. Implies the tiered "
                        "cache even with --tiered-cache off")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked prefill: consume prompts <= N tokens per "
                        "program, <= 1 prefill program per scheduler "
                        "iteration — bounds how long a cold long prompt "
                        "can stall running sessions' decode (and lifts "
                        "the prompt-length cap). 0 = off (monolithic "
                        "bucketed prefill)")
    # --- online autotuner (serve/autotune.py) ---
    p.add_argument("--autotune", type=str, default="off",
                   choices=["on", "off"],
                   help="online serve autotuner: a controller thread "
                        "watches WINDOWED deltas of the live TTFT/ITL/"
                        "queue-wait histograms + tier occupancy/spill-"
                        "thrash counters and moves the decode-window "
                        "cap, the prefill-chunk size, the host-tier "
                        "bound and the best-effort admission fraction — "
                        "each within PRE-WARMED bounds, so it can never "
                        "trigger a mid-traffic compile. Decisions land "
                        "in /stats 'autotune' + serve_autotune_moves_"
                        "total{knob,direction}. Needs --telemetry on. "
                        "'off' (default) = today's static operating "
                        "point, byte-identical")
    p.add_argument("--autotune-interval", type=float, default=0.25,
                   help="seconds between autotuner control windows "
                        "(each window reads one histogram delta)")
    p.add_argument("--slo-ms", type=float, default=250.0,
                   help="the TTFT p99 SLO (ms) the autotuner protects: "
                        "pressure/headroom thresholds are fractions of "
                        "it (smaller K / larger chunks as the p99 "
                        "approaches it; larger K only well below it)")
    p.add_argument("--autotune-chunks", type=str, default=None,
                   help="warmed prefill-chunk choice set the autotuner "
                        "moves --prefill-chunk among (comma list; each "
                        "entry must satisfy the same bucket/stride "
                        "constraints as --prefill-chunk). Default: "
                        "half/base/double of --prefill-chunk, invalid "
                        "entries dropped. Ignored without "
                        "--prefill-chunk")
    p.add_argument("--autotune-host-tier-max", type=int, default=0,
                   help="ceiling the autoscaler leg may grow "
                        "--host-tier-entries to under spill thrash "
                        "(0 = 4x the configured entries)")
    p.add_argument("--autotune-be-floor", type=float, default=0.1,
                   help="lowest best-effort admission fraction the "
                        "autotuner may tighten --best-effort-queue-frac "
                        "to when the state plane thrashes at its "
                        "capacity ceiling")
    # --- model registry + rolling rollout (serve/registry.py, rollout.py) ---
    p.add_argument("--registry-dir", type=str, default=None,
                   help="model registry directory (serve/registry.py): "
                        "attaches a rollout controller so POST /rollout "
                        "(or a supervising trainer's publication) can "
                        "roll a new model version across the replicas "
                        "WITHOUT a restart — drain one replica (kept "
                        "sessions migrate, queued work requeues), swap "
                        "params, re-warm the compile-key lattice "
                        "off-path, rejoin; one replica at a time, so "
                        "capacity never drops below N-1. Also unlocks "
                        "the autotuner's device-slot capacity leg")
    p.add_argument("--model-id", type=str, default="default",
                   help="model id this fleet boots as (the registry/"
                        "routing namespace for the checkpoint loaded at "
                        "startup; requests with no 'model' field route "
                        "here)")
    p.add_argument("--canary-every", type=int, default=0,
                   help="canary routing during a rollout: shadow every "
                        "Nth stateless request onto the first upgraded "
                        "replica and token-diff its output against the "
                        "primary before rolling the rest (report in "
                        "/rollout 'last_canary' + serve_canary_diff_"
                        "total{verdict}). 0 = no canary phase")
    p.add_argument("--require-canary-match", action="store_true",
                   help="abort the rollout (outcome 'canary_regression') "
                        "when any canary pair token-diffs; without it "
                        "the diff report is informational (sampled "
                        "traffic diffs legitimately)")
    # --- speculative decoding (train/distill.py, serve/engine.py) ---
    p.add_argument("--speculative", action="store_true",
                   help="lossless speculative decoding: a distilled "
                        "DRAFT LM (published by `cli distill`, loaded "
                        "from --registry-dir as a verified pair with "
                        "the target) proposes K_draft tokens per step "
                        "and the target verifies all of them in ONE "
                        "teacher-forced window pass — greedy output is "
                        "token-identical to plain decode by "
                        "construction, rejection is an O(1) carry "
                        "restore. Applies to greedy default-model "
                        "traffic; sampled/named-model requests decode "
                        "plain. Requires --registry-dir "
                        "(docs/OPERATIONS.md 'Speculative decoding')")
    p.add_argument("--draft-model", type=str, default=None,
                   help="registry id of the draft artifact (default: "
                        "'<--model-id>-draft', the id `cli distill` "
                        "publishes under). Its config_hash/parent "
                        "record must verify against the serving "
                        "target or boot refuses the pair")
    p.add_argument("--spec-ladder", type=str, default="2,4",
                   help="warmed K_draft rungs the speculative window "
                        "can dispatch (comma list; rung 0 = plain "
                        "decode is always included). Each rung is one "
                        "compile key per batch bucket, all covered by "
                        "warmup — the autotuner's spec_k knob moves "
                        "within this set")
    p.add_argument("--spec-k", type=int, default=None,
                   help="initial K_draft (must be a --spec-ladder rung "
                        "or 0; default: the top rung). 0 starts at "
                        "plain decode with speculation armed — the "
                        "autotuner can still probe upward")
    # --- per-tenant rate limiting (serve/router.py) ---
    p.add_argument("--tenant-rate", type=float, default=0,
                   help="per-tenant token-bucket rate limit (requests/s "
                        "per distinct 'tenant' request field) on top of "
                        "the class policy; over-rate requests 429 with "
                        "an honest Retry-After (time to the next token, "
                        "floored by the shared queue-drain policy). "
                        "0 = off; untenanted requests are never limited")
    p.add_argument("--tenant-burst", type=float, default=5.0,
                   help="token-bucket burst allowance per tenant "
                        "(requests that may arrive back-to-back before "
                        "the rate limit engages)")
    # --- sampling defaults (selftest is always greedy) ---
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--greedy", action="store_true")
    # --- selftest workload ---
    p.add_argument("--sessions", type=int, default=8)
    p.add_argument("--max-new-tokens", type=int, default=16)
    # --- endpoint / observability ---
    p.add_argument("--host", type=str, default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--telemetry", type=str, default="on",
                   choices=["on", "off"],
                   help="metrics registry (obs/): 'on' serves GET /metrics "
                        "(Prometheus text exposition: server-side TTFT/ITL/"
                        "queue-wait histograms, compile + cache counters) "
                        "and histogram summaries in /stats; 'off' swaps in "
                        "no-op instruments (near-zero record cost) and "
                        "/metrics reports telemetry disabled")
    p.add_argument("--trace", type=str, default=None,
                   help="host-side span trace output (Chrome trace JSON; "
                        "includes one admit→queue→prefill→decode→readback "
                        "timeline row per request — open in Perfetto)")
    p.add_argument("--faults", type=str, default=None,
                   help="ARM FAULT INJECTION (chaos drills only): e.g. "
                        "'serve_error@2' raises from the 2nd decode call "
                        "— resilience/faults.py grammar, same flag as the "
                        "training CLI; also armable via LSTM_TSP_FAULTS")
    return p


def _parse_window_ladder(spec: str) -> tuple[int, ...]:
    """--decode-window → a Batcher window ladder: 'auto' = the default
    ladder (1, 4, 8); an int N = that ladder capped at N, with N itself
    as the top rung (so `--decode-window 8` == auto, `6` → (1, 4, 6),
    `1` pins the per-token path)."""
    from .serve import Batcher

    if spec.strip().lower() == "auto":
        return Batcher.DEFAULT_WINDOW_LADDER
    try:
        n = int(spec)
    except ValueError:
        raise SystemExit(
            f"--decode-window: expected 'auto' or a positive int, got "
            f"{spec!r}")
    if n < 1:
        raise SystemExit(f"--decode-window: window must be >= 1, got {n}")
    return tuple(sorted(
        {1, n} | {k for k in Batcher.DEFAULT_WINDOW_LADDER if k < n}
    ))


def _parse_spec_ladder(spec: str) -> tuple[int, ...]:
    """--spec-ladder → the warmed K_draft rung set (rung 0 — plain
    decode — is always added by the Batcher)."""
    try:
        rungs = tuple(int(x) for x in spec.split(",") if x.strip())
    except ValueError:
        raise SystemExit(
            f"--spec-ladder: expected comma-separated ints, got {spec!r}")
    if not rungs or any(k < 1 for k in rungs):
        raise SystemExit(
            f"--spec-ladder: need at least one rung >= 1, got {spec!r}")
    return rungs


def _autotune_chunk_choices(args, chunk: int | None) -> tuple[int, ...] | None:
    """The warmed prefill-chunk choice set the autotuner moves among.
    Explicit ``--autotune-chunks`` entries must each satisfy the same
    bucket/stride constraints as ``--prefill-chunk`` (fail fast with the
    flag's own message); the derived default is half/base/double of the
    configured chunk with invalid candidates silently dropped. None when
    chunking is off — the chunk knob stays pinned."""
    if chunk is None:
        if args.autotune_chunks:
            raise SystemExit(
                "--autotune-chunks needs --prefill-chunk (the knob moves "
                "among chunk sizes, it cannot turn chunking on)")
        return None
    max_bucket = max(_parse_buckets(args.prefill_buckets,
                                    "--prefill-buckets"))

    def ok(c: int) -> bool:
        if c < 1 or c > max_bucket:
            return False
        return (args.prefix_cache != "on" or c % args.prefix_stride == 0
                or args.prefix_stride % c == 0)

    if args.autotune_chunks:
        try:
            choices = tuple(int(x) for x in args.autotune_chunks.split(",")
                            if x.strip())
        except ValueError:
            raise SystemExit(
                f"--autotune-chunks: expected comma-separated ints, got "
                f"{args.autotune_chunks!r}")
        bad = [c for c in choices if not ok(c)]
        if not choices or bad:
            raise SystemExit(
                f"--autotune-chunks: entries must be in [1, {max_bucket}] "
                f"and stride-compatible with --prefix-stride "
                f"{args.prefix_stride}; bad: {bad or 'empty'}")
        return tuple(sorted(set(choices) | {chunk}))
    derived = {c for c in (chunk // 2, chunk, chunk * 2) if ok(c)}
    return tuple(sorted(derived | {chunk}))


def _positive_int(text: str) -> int:
    n = int(text)
    if n < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {n}")
    return n


def _parse_buckets(spec: str, flag: str) -> tuple[int, ...]:
    try:
        buckets = tuple(int(x) for x in spec.split(",") if x.strip())
    except ValueError:
        raise SystemExit(f"{flag}: expected comma-separated ints, got {spec!r}")
    if not buckets or any(b < 1 for b in buckets):
        raise SystemExit(f"{flag}: need at least one positive bucket")
    return buckets


def _build_serve_stack(args, n_replicas: int = 1):
    """(params, cfg, started-server) from the serve flags.

    ``n_replicas`` > 1 builds one engine per replica (each with its own
    state/prefix caches and compiled programs) behind the admission
    router; when the host exposes multiple accelerators the engines are
    committed round-robin across ``jax.devices()`` (device-per-replica),
    otherwise they share the one device (thread-per-replica)."""
    from .models import LMConfig, init_lm
    from .serve import ServeEngine, ServeServer

    if getattr(args, "model_file", None):
        return _build_decoder_stack(args, n_replicas)
    # the two caches default ON for this family (None = not given)
    args.prefix_cache = args.prefix_cache or "on"
    args.tiered_cache = args.tiered_cache or "on"
    chunk = args.prefill_chunk or None
    if (chunk is not None and chunk > 0 and args.prefix_cache == "on"
            and chunk % args.prefix_stride != 0
            and args.prefix_stride % chunk != 0):
        # same constraint Batcher.__init__ enforces, checked here so a bad
        # flag combo fails in ms, before params init / checkpoint restore
        raise SystemExit(
            f"--prefill-chunk {chunk} must be a multiple or divisor of "
            f"--prefix-stride {args.prefix_stride} (chunk stops are "
            "stride-aligned prefix insert points), or use --prefix-cache off")
    cfg = LMConfig(
        vocab_size=args.vocab_size,
        hidden_size=args.hidden_units,
        num_layers=args.num_layers,
        tie_embeddings=args.tie_embeddings,
        compute_dtype=args.compute_dtype,
    )
    params = init_lm(jax.random.PRNGKey(args.seed), cfg)
    if args.checkpoint_dir:
        from .train.checkpoint import Checkpointer

        ckpt = Checkpointer(args.checkpoint_dir)
        if not ckpt.has_checkpoint():
            raise SystemExit(f"no checkpoint in {args.checkpoint_dir}")
        params = ckpt.restore_latest_params(params)
        if params is None:
            # every checkpoint failed verification and was quarantined
            # (train/checkpoint.py) — refuse to serve random init
            raise SystemExit(
                f"every checkpoint in {args.checkpoint_dir} is corrupt "
                "(now quarantined); refusing to serve an untrained model")
        params = jax.device_get(params)
    from .obs import NULL_REGISTRY, REGISTRY

    registry = (NULL_REGISTRY if getattr(args, "telemetry", "on") == "off"
                else REGISTRY)
    devices = jax.devices()
    shards = int(getattr(args, "mesh_shards", 1) or 1)
    if shards < 1:
        raise SystemExit(f"--mesh-shards must be >= 1, got {shards}")
    if shards > 1 and len(devices) < shards:
        raise SystemExit(
            f"--mesh-shards {shards} needs {shards} devices, host has "
            f"{len(devices)} (on CPU set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N)")

    def _mesh_devices(i: int):
        """Replica i's device group: disjoint groups when the host has
        replicas*shards devices (mesh-per-replica), the shared leading
        group otherwise (thread-per-replica over one mesh — the CPU
        virtual-device analog of thread-per-replica on one chip)."""
        if shards == 1:
            return None
        if len(devices) >= n_replicas * shards:
            return devices[i * shards:(i + 1) * shards]
        return devices[:shards]
    engines = [
        ServeEngine(
            params, cfg,
            num_slots=args.num_slots,
            prefill_buckets=_parse_buckets(args.prefill_buckets,
                                           "--prefill-buckets"),
            batch_buckets=_parse_buckets(args.batch_buckets,
                                         "--batch-buckets"),
            # distinct per-replica sampling chains (greedy is unaffected)
            rng_seed=args.seed + i,
            prefix_cache=args.prefix_cache == "on",
            prefix_stride=args.prefix_stride,
            prefix_entries=args.prefix_entries,
            # prefix-state fabric: the radix-trie store supersedes the
            # exact-match cache when on (engine picks trie over cache)
            prefix_fabric=getattr(args, "prefix_fabric", "off") == "on",
            prefix_nodes=getattr(args, "prefix_nodes", 64),
            prefix_host_mb=getattr(args, "prefix_host_mb", 64.0),
            # tiered session-state cache: host-RAM spill of evicted
            # slots + durable disk tier / restart-surviving session
            # checkpoints under --session-dir (shared by all replicas —
            # session files are replica-agnostic, so any replica can
            # restore any session after a restart)
            tiered_cache=args.tiered_cache == "on",
            host_tier_entries=args.host_tier_entries,
            session_dir=args.session_dir,
            # the registry/routing namespace the boot checkpoint serves
            # under (requests with no 'model' field route here)
            model_id=getattr(args, "model_id", "default"),
            replica=i,
            decode_kernel=args.decode_kernel,
            # one registry argument scopes the whole serve stack's
            # telemetry (engine, caches, batcher, router, /metrics);
            # off = no-op instruments
            registry=registry,
            # mesh-per-replica (--mesh-shards > 1) or device-per-replica
            # when the host has more than one device
            mesh_shards=shards,
            mesh_devices=_mesh_devices(i),
            device=(devices[i % len(devices)]
                    if shards == 1 and len(devices) > 1 else None),
        )
        for i in range(n_replicas)
    ]
    spec_kw = {}
    if getattr(args, "speculative", False):
        if not getattr(args, "registry_dir", None):
            raise SystemExit(
                "--speculative needs --registry-dir (the draft loads "
                "from the registry as a verified pair with the target; "
                "publish one with `cli distill`)")
        if shards > 1:
            raise SystemExit(
                "--speculative is not supported with --mesh-shards > 1 "
                "(the draft's state is replica-local)")
        from .train.distill import load_draft

        try:
            dmeta, dparams, dcfg = load_draft(
                args.registry_dir,
                cfg,
                teacher_id=getattr(args, "model_id", "default"),
                draft_id=getattr(args, "draft_model", None) or None,
            )
        except Exception as e:
            raise SystemExit(f"--speculative: cannot load draft: {e}")
        for eng in engines:
            eng.attach_draft(dparams, dcfg, version=dmeta["version"])
        spec_kw = {
            "speculative": True,
            "spec_ladder": _parse_spec_ladder(
                getattr(args, "spec_ladder", "2,4")),
        }
        if getattr(args, "spec_k", None) is not None:
            spec_kw["spec_k"] = args.spec_k
    wp, wb = _parse_class_weights(args)
    autotune_cfg = None
    chunk_choices = None
    if getattr(args, "autotune", "off") == "on":
        if getattr(args, "telemetry", "on") == "off":
            # the controller steers on the live histograms — a blind
            # controller would simply never move, which reads like a bug
            raise SystemExit(
                "--autotune on needs --telemetry on (the controller "
                "watches the live serve histograms)")
        from .serve import AutoTuneConfig

        if args.autotune_interval <= 0:
            raise SystemExit(
                f"--autotune-interval must be > 0, got "
                f"{args.autotune_interval}")
        if args.slo_ms <= 0:
            raise SystemExit(f"--slo-ms must be > 0, got {args.slo_ms}")
        chunk_choices = _autotune_chunk_choices(args, chunk)
        autotune_cfg = AutoTuneConfig(
            interval_s=args.autotune_interval,
            slo_s=args.slo_ms / 1e3,
            host_tier_max=args.autotune_host_tier_max or None,
            best_effort_floor=args.autotune_be_floor,
        )
    server = ServeServer(engines if n_replicas > 1 else engines[0],
                         max_active=args.max_active,
                         queue_size=args.queue_size,
                         window_ladder=_parse_window_ladder(args.decode_window),
                         prefill_chunk=args.prefill_chunk or None,
                         prefill_chunk_choices=chunk_choices,
                         autotune=autotune_cfg,
                         tenant_rate=getattr(args, "tenant_rate", 0) or None,
                         tenant_burst=getattr(args, "tenant_burst", 5.0),
                         class_weights=(wp, wb),
                         health_stale_after=args.replica_stale_s,
                         best_effort_queue_frac=args.best_effort_queue_frac,
                         sweep_interval=args.replica_sweep_s or None,
                         deadline_defaults={
                             "priority": args.deadline_priority_s or None,
                             "best_effort":
                                 args.deadline_best_effort_s or None,
                         },
                         remote_replicas=tuple(
                             getattr(args, "remote_replica", []) or ()),
                         remote_timeout_s=getattr(
                             args, "remote_timeout_s", 120.0),
                         model_registry=getattr(args, "registry_dir",
                                                None) or None,
                         rollout_kw={
                             "canary_every":
                                 getattr(args, "canary_every", 0),
                             "require_canary_match":
                                 getattr(args, "require_canary_match",
                                         False),
                         },
                         **spec_kw)
    return params, cfg, server


def _parse_class_weights(args) -> tuple[int, int]:
    """--class-weights 'P,B': fail in ms with the flag's own message, not a
    Batcher traceback mid-stack-build."""
    try:
        wp, wb = (int(x) for x in args.class_weights.split(","))
    except ValueError:
        raise SystemExit(
            f"--class-weights: expected 'P,B' positive ints, got "
            f"{args.class_weights!r}")
    if wp < 1 or wb < 1:
        raise SystemExit(
            f"--class-weights: weights must be >= 1, got "
            f"{args.class_weights!r}")
    return wp, wb


def _refuse_for_decoder(args, n_replicas: int) -> None:
    """The LSTM-only features, refused by name when a decoder is served:
    a flag that would be silently ignored is a wrong answer waiting."""
    asked = {
        "--prefix-cache on": args.prefix_cache == "on",
        "--prefix-fabric on": getattr(args, "prefix_fabric", "off") == "on",
        "--tiered-cache on": args.tiered_cache == "on",
        "--session-dir": bool(args.session_dir),
        "--speculative": bool(getattr(args, "speculative", False)),
        "--mesh-shards > 1": int(getattr(args, "mesh_shards", 1) or 1) > 1,
        "--replicas > 1": n_replicas > 1,
        "--remote-replica": bool(getattr(args, "remote_replica", None)),
        "--checkpoint-dir": bool(args.checkpoint_dir),
        "--registry-dir": bool(getattr(args, "registry_dir", None)),
        "--autotune on": getattr(args, "autotune", "off") == "on",
        "--decode-kernel pallas/scan": args.decode_kernel != "auto",
        "sampled decoding (pass --greedy)": not args.greedy,
    }
    bad = [flag for flag, on in asked.items() if on]
    if bad:
        raise SystemExit(
            "--model-file serves a decoder; LSTM-only for now and refused "
            f"with it: {', '.join(bad)}. (A decoder's sessions live in a "
            "paged latent cache: no prefix sharing over pages, no spill "
            "tier, no draft model yet — ROADMAP.md.)")


def _build_decoder_stack(args, n_replicas: int = 1):
    """(params, cfg, server) for ``--model-file``: the same `ServeServer`
    -> router -> `Batcher` stack over a `DecoderEngine` and its paged
    cache, whichever block the file's ``model_type`` names. Weights are
    random from the FILE's seed
    (``assumed.weights_seed``), never from --seed: one file is one model."""
    from .models import decoder
    from .obs import NULL_REGISTRY, REGISTRY
    from .serve import ServeServer
    from .serve.engine import build_engine

    _refuse_for_decoder(args, n_replicas)
    cfg, doc = decoder.load_model_file(args.model_file)
    registry = (NULL_REGISTRY if getattr(args, "telemetry", "on") == "off"
                else REGISTRY)
    page = args.page_size
    if cfg.grouped:
        if args.kv_window_gib is None:
            raise SystemExit(
                f"--model-file {args.model_file} (model_type "
                f"{cfg.model_type}) keeps two kinds of page: give "
                "--kv-window-gib, the window layers' part of --kv-pool-gib")
        gib = (args.kv_pool_gib - args.kv_window_gib, args.kv_window_gib)
        flag = "--kv-pool-gib/--kv-window-gib"
    else:
        gib, flag = (args.latent_pool_gib,), "--latent-pool-gib"
    # a kind's page: `page` rows of `latent_width` bf16 lanes in each of
    # the kind's layers
    page_bytes = [page * cfg.latent_width * 2 * cfg.layer_kinds.count(k)
                  for k in range(len(gib))]
    num_pages = tuple(int(g * 2 ** 30) // b for g, b in zip(gib, page_bytes))
    if min(num_pages) < 1:
        raise SystemExit(
            f"{flag} {gib} hold no page of {page} tokens ({page_bytes} "
            "bytes a page)")
    params = decoder.init_decoder(
        int(doc.get("assumed", {}).get("weights_seed", 0)), cfg,
        dtype=np.dtype(args.weights_dtype))
    engine = build_engine(
        params, cfg, num_slots=args.num_slots, num_pages=num_pages,
        page=page, max_context=args.max_context,
        prefill_buckets=_parse_buckets(args.prefill_buckets,
                                       "--prefill-buckets"),
        batch_buckets=_parse_buckets(args.batch_buckets, "--batch-buckets"),
        max_prefill_rows=args.prefill_rows, registry=registry,
        model_id=getattr(args, "model_id", "default"),
        interpret=args.interpret_kernels)
    wp, wb = _parse_class_weights(args)
    server = ServeServer(
        engine, max_active=args.max_active, queue_size=args.queue_size,
        window_ladder=_parse_window_ladder(args.decode_window),
        # a prompt longer than the largest bucket is prefilled in chunks
        # between decode steps: always on for this family
        prefill_chunk=args.prefill_chunk or engine.max_prompt_len,
        tenant_rate=getattr(args, "tenant_rate", 0) or None,
        tenant_burst=getattr(args, "tenant_burst", 5.0),
        class_weights=(wp, wb),
        health_stale_after=args.replica_stale_s,
        best_effort_queue_frac=args.best_effort_queue_frac,
        sweep_interval=args.replica_sweep_s or None,
        deadline_defaults={"priority": args.deadline_priority_s or None,
                           "best_effort": args.deadline_best_effort_s or None})
    return params, cfg, server


def _serve_sampling(args):
    from .serve import SamplingParams

    return SamplingParams(temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p, greedy=args.greedy)


def _serve_selftest(args) -> int:
    """Acceptance check: a batch of concurrent sessions decoded through the
    full server path must be token-identical to `models/generate.py` with
    the same params/prompt (greedy)."""
    import json
    import threading

    from .models import make_generate_fn
    from .models.generate import judge_greedy_divergence
    from .serve import InprocessClient

    if getattr(args, "model_file", None):
        return _serve_selftest_decoder(args)
    params, cfg, server = _build_serve_stack(args, args.replicas)
    rng = np.random.RandomState(args.seed)
    lengths = [3, 5, 8, 13, 2, 7][: max(args.sessions, 2)]
    while len(lengths) < args.sessions:
        lengths.append(int(rng.randint(2, min(21, server.engine.max_prompt_len))))
    prompts = [rng.randint(0, cfg.vocab_size, size=t).astype(np.int32)
               for t in lengths]
    n_new = args.max_new_tokens

    got: list[list[int] | None] = [None] * len(prompts)
    errors: list[str] = []
    client = InprocessClient(server)

    def run_one(i):
        try:
            got[i] = client.generate(prompts[i], max_new_tokens=n_new)
        except Exception as e:  # surface, don't hang the join
            errors.append(f"session {i}: {type(e).__name__}: {e}")

    with server:
        threads = [threading.Thread(target=run_one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    if errors:
        print("\n".join(errors))
        print("serve selftest: FAIL (request errors)")
        return 1
    gen = make_generate_fn(cfg, max_new_tokens=n_new, greedy=True)
    # Batched and single-sequence greedy decode run different programs, so
    # near-tied logits may round to different picks (wide vocabulary,
    # bf16). A mismatch is judged against the plain float32 reference
    # (models/generate.judge_greedy_divergence): ties are stated, printed
    # and counted; only real mismatches fail.
    bad = ties = 0
    for i, prompt in enumerate(prompts):
        ref = np.asarray(gen(params, prompt[None, :],
                             jax.random.PRNGKey(args.seed)))[0, prompt.size:]
        verdict, detail = judge_greedy_divergence(
            params, cfg, prompt, np.asarray(got[i], np.int32), ref)
        if verdict == "equal":
            continue
        bad += 1
        ties += verdict == "tie"
        print(f"session {i}: MISMATCH serve={got[i]} ref={ref.tolist()} "
              f"— {detail}")
    engine = server.engine
    stats = server.stats()
    print(json.dumps({
        "note": "serve_selftest", "sessions": len(prompts),
        "tokens_per_session": n_new, "mismatches": bad,
        "mismatches_tied": ties,
        "decode_kernel": stats["decode_kernel"],
        "compiles_prefill": engine.num_compiles("prefill"),
        "compiles_decode": engine.num_compiles("decode"),
        "compiles_decode_window": engine.num_compiles("decode_window"),
        "compiles_decode_window_pallas":
            engine.num_compiles("decode_window_pallas"),
        "decode_window_scan_fallbacks":
            stats["decode_window_scan_fallbacks"],
        # where each replica's arrays live, and what it served
        # (a remote replica's device is its own host's to report)
        "replicas": [{"replica": r["replica"], "device": r.get("device"),
                      "completed": r["batcher"]["completed"]}
                     for r in stats["replicas"]],
        **stats["batcher"],
    }))
    ok = bad == ties
    print(f"serve selftest: {'PASS' if ok else 'FAIL'}"
          + (f" ({ties} rounding tie(s), judged above)" if ties else ""))
    return 0 if ok else 1


def _serve_selftest_decoder(args) -> int:
    """``--selftest`` with ``--model-file``: concurrent sessions of two
    turns each through the full server path (packed and chunked prefill,
    decode windows, kept sessions continuing from their pages), then every
    conversation again ALONE, one token a step — other programs, other
    batch shapes. The greedy tokens must be the same; where they part, the
    two picks' logits must be a rounding tie (2^-6 of the logit: bf16
    inputs), as the LSTM selftest judges its two paths."""
    import json
    import threading

    from .serve import ServeServer

    _, cfg, server = _build_serve_stack(args, 1)
    sampling = _serve_sampling(args)
    rng = np.random.RandomState(args.seed)
    long = server.engine.max_prompt_len + 9       # one chunked prompt
    lengths = ([3, long, 8, 13, 21, 5] * args.sessions)[: max(args.sessions, 2)]
    n_new = args.max_new_tokens
    draw = lambda n: rng.randint(2, cfg.vocab_size, size=n).astype(np.int32)  # noqa: E731
    turns = [(draw(t), draw(4)) for t in lengths]

    def converse(srv, i, out):
        try:
            a = srv.generate(turns[i][0], max_new_tokens=n_new,
                             sampling=sampling, keep_session=True)
            b = srv.generate(
                np.concatenate([[a.tokens[-1]], turns[i][1]]),
                max_new_tokens=n_new, sampling=sampling,
                session_id=a.session_id)
            out[i] = (a.tokens + b.tokens,
                      a.token_logits + b.token_logits)
        except Exception as e:  # surface, don't hang the join
            out[i] = f"session {i}: {type(e).__name__}: {e}"

    together: list = [None] * len(turns)
    with server:
        threads = [threading.Thread(target=converse, args=(server, i, together))
                   for i in range(len(turns))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    alone: list = [None] * len(turns)
    solo = ServeServer(server.engine, max_active=1, window_ladder=(1,),
                       prefill_chunk=server.engine.max_prompt_len)
    with solo:
        for i in range(len(turns)):
            converse(solo, i, alone)
    errors = [x for x in together + alone if isinstance(x, str)]
    bad = ties = 0
    for i, (a, b) in enumerate(zip(together, alone)):
        if errors or a[0] == b[0]:
            continue
        j = next(k for k, (x, y) in enumerate(zip(a[0], b[0])) if x != y)
        la, lb = a[1][j][0], b[1][j][0]
        tie = abs(la - lb) <= 2.0 ** -6 * max(abs(la), abs(lb))
        ties += tie
        bad += not tie
        print(f"session {i}: token {j} {a[0][j]} vs {b[0][j]}, logits "
              f"{la:.5f} vs {lb:.5f}: {'a rounding tie' if tie else 'REAL'}")
    cache = server.engine.cache.stats()
    leaked = server.engine.cache.pages_in_use or cache["live_sessions"]
    print(json.dumps({
        "note": "serve_selftest", "family": "decoder",
        "sessions": len(turns), "tokens_per_turn": n_new,
        "mismatches": bad, "mismatches_tied": ties, "errors": errors,
        "cache": cache, **server.engine.stats()["decoder"]}))
    ok = not errors and not bad and not leaked
    print(f"serve selftest: {'PASS' if ok else 'FAIL'}"
          + (" (pages or sessions left behind)" if leaked else ""))
    return 0 if ok else 1


def _serve_http(args) -> int:
    from .serve.server import make_http_server

    _, _, server = _build_serve_stack(args, args.replicas)
    # pre-compile the bucket lattice for the default sampling config BEFORE
    # taking traffic: on TPU a compile is ~20-40 s, which would both time
    # out first requests and starve the scheduler heartbeat long enough to
    # flip /healthz 503 on a healthy warming server (an orchestrator would
    # then kill-loop it). The selftest warms implicitly; --http must too.
    print(f"serve: warming the compile lattice "
          f"({len(server.replicas)} replica(s))...", flush=True)
    n = server.warmup(_serve_sampling(args),
                      prompt_lens=tuple(server.engine.prefill_buckets))
    print(f"serve: {n} programs compiled across "
          f"{len(server.replicas)} replica(s)", flush=True)
    httpd = make_http_server(server, args.host, args.port)
    host, port = httpd.server_address[:2]
    print(f"serving on http://{host}:{port} (POST /v1/generate, "
          "GET /healthz, GET /v1/stats, GET /metrics) — ctrl-C to stop",
          flush=True)
    with server:
        try:
            httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            httpd.server_close()
    return 0


def _run_serve(argv) -> int:
    args = build_serve_parser().parse_args(argv)
    place_compile_cache()
    from .resilience import faults

    # serve chaos drills (serve_error@N): flag wins, env is the fallback
    faults.arm_from_flag_or_env(args.faults)
    from .utils import Tracer, set_tracer

    tracer = None
    if args.trace:
        tracer = Tracer()
        set_tracer(tracer)
    try:
        if args.selftest:
            return _serve_selftest(args)
        return _serve_http(args)
    finally:
        if tracer is not None:
            set_tracer(None)
            try:
                tracer.save(args.trace)
            except OSError as e:
                print(f"warning: could not write --trace file: {e}")


def build_distill_parser() -> argparse.ArgumentParser:
    """``distill`` subcommand: train + publish a speculative-decoding
    draft LM against a trained target (train/distill.py)."""
    p = argparse.ArgumentParser(
        prog="lstm_tensorspark_tpu distill",
        description="distill a draft LM (H/4, 1 layer, shared vocab) "
                    "against a trained target's logits with a KL+CE "
                    "mixed loss, and publish it to the model registry "
                    "as a verified pair — the artifact `serve "
                    "--speculative` loads",
    )
    # --- teacher (must match the producing training run) ---
    p.add_argument("--vocab-size", type=int, default=89)
    p.add_argument("--hidden-units", type=int, default=64)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--tie-embeddings", action="store_true")
    p.add_argument("--compute-dtype", type=str, default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--registry-dir", type=str, required=True,
                   help="model registry (serve/registry.py): the "
                        "teacher loads from here when --checkpoint-dir "
                        "is not given, and the draft publishes here as "
                        "'<--model-id>-draft'")
    p.add_argument("--model-id", type=str, default="default",
                   help="the teacher's registry id (the id the serving "
                        "fleet boots as)")
    p.add_argument("--draft-id", type=str, default=None,
                   help="publish the draft under this id instead of "
                        "'<--model-id>-draft'")
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   help="restore the teacher from a training run's "
                        "checkpoints instead of the registry (the model "
                        "flags must match it)")
    # --- corpus (the logit-harvest stream) ---
    p.add_argument("--data-path", type=str, default=None,
                   help="corpus directory; an error if the dataset's "
                        "files are not there (without this flag: the "
                        "synthetic stand-in)")
    p.add_argument("--dataset", type=str, default="ptb_char",
                   choices=list(LM_DATASETS))
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--seq-len", type=int, default=32)
    # --- distillation ---
    p.add_argument("--steps", type=int, default=200,
                   help="draft optimizer steps (each scores one [B,T] "
                        "window through the teacher first)")
    p.add_argument("--alpha", type=float, default=0.5,
                   help="KL(teacher||student) weight in [0,1]; 1-alpha "
                        "weights the hard-label cross-entropy")
    p.add_argument("--distill-temperature", type=float, default=2.0,
                   help="softmax temperature of the KL term (Hinton "
                        "tau; the loss scales by tau^2)")
    p.add_argument("--distill-optimizer", type=str, default="adam",
                   choices=["sgd", "momentum", "adam", "adamw", "rmsprop"])
    p.add_argument("--distill-lr", type=float, default=1e-3)
    p.add_argument("--log-every", type=int, default=50)
    p.add_argument("--jsonl", type=str, default=None,
                   help="metrics JSONL path for the distill run")
    return p


def _run_distill(argv) -> int:
    args = build_distill_parser().parse_args(argv)
    place_compile_cache()
    import json

    from .data.batching import lm_batch_stream
    from .data.datasets import get_dataset
    from .models import LMConfig, init_lm
    from .serve.registry import ModelRegistry, config_fingerprint
    from .train.distill import distill, publish_draft
    from .train.metrics import MetricsLogger

    cfg = LMConfig(
        vocab_size=args.vocab_size,
        hidden_size=args.hidden_units,
        num_layers=args.num_layers,
        tie_embeddings=args.tie_embeddings,
        compute_dtype=args.compute_dtype,
    )
    registry = ModelRegistry(args.registry_dir)
    if args.checkpoint_dir:
        from .train.checkpoint import Checkpointer

        ckpt = Checkpointer(args.checkpoint_dir)
        if not ckpt.has_checkpoint():
            raise SystemExit(f"no checkpoint in {args.checkpoint_dir}")
        tparams = ckpt.restore_latest_params(
            init_lm(jax.random.PRNGKey(args.seed), cfg))
        if tparams is None:
            raise SystemExit(
                f"every checkpoint in {args.checkpoint_dir} is corrupt "
                "(now quarantined); refusing to distill an untrained "
                "teacher")
        tparams = jax.device_get(tparams)
    else:
        template = init_lm(jax.random.PRNGKey(args.seed), cfg)
        try:
            meta, tparams = registry.load_params(args.model_id, template)
        except Exception as e:
            raise SystemExit(
                f"cannot load teacher {args.model_id!r} from "
                f"{args.registry_dir}: {e} (publish one, or pass "
                "--checkpoint-dir)")
        if (meta.get("config_hash")
                and meta["config_hash"] != config_fingerprint(cfg)):
            raise SystemExit(
                f"teacher {args.model_id} v{meta['version']} was "
                f"published for config {meta['config_hash']}, the model "
                f"flags describe {config_fingerprint(cfg)} — align the "
                "flags with the producing run")
    ds = get_dataset(args.dataset, args.data_path)
    if len(ds["vocab"]) > cfg.vocab_size:
        raise SystemExit(
            f"corpus vocab ({len(ds['vocab'])}) exceeds --vocab-size "
            f"({cfg.vocab_size}); the teacher cannot score tokens "
            "outside its embedding")
    logger = MetricsLogger(jsonl_path=args.jsonl)
    dparams, dcfg = distill(
        tparams, cfg, lm_batch_stream(ds["train"], args.batch_size,
                                      args.seq_len),
        num_steps=args.steps, alpha=args.alpha,
        temperature=args.distill_temperature,
        optimizer=args.distill_optimizer, learning_rate=args.distill_lr,
        seed=args.seed, log_every=args.log_every, logger=logger,
    )
    meta = publish_draft(registry, dparams, dcfg, cfg,
                         teacher_id=args.model_id, draft_id=args.draft_id)
    print(json.dumps({
        "distill": {
            "draft": meta["model"], "version": meta["version"],
            "hidden_size": dcfg.hidden_size,
            "num_layers": dcfg.num_layers,
            "config_hash": meta["config_hash"],
            "parent": meta["parent"],
            "payload_bytes": meta["payload_bytes"],
            "steps": args.steps,
        }
    }))
    return 0


def _run_classifier(args, logger) -> int:
    from .tasks.classification import run_classifier
    return run_classifier(args, logger)


def _run_forecaster(args, logger) -> int:
    from .tasks.forecasting import run_forecaster
    return run_forecaster(args, logger)


if __name__ == "__main__":
    raise SystemExit(main())
