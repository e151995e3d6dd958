"""UCI-Electricity seq2seq forecasting task runner (BASELINE.md config 4).

Teacher-forced MSE training of the encoder-decoder LSTM
(models/seq2seq.py) via the shared cli._setup_training orchestration
(single-chip or DP, checkpoint/resume), with free-running autoregressive
evaluation on the held-out tail of the series.
"""

from __future__ import annotations

import jax
import numpy as np


def run_forecaster(args, logger) -> int:
    from ..cli import (_make_logged_loop, _mfu_logging, _setup_training,
                       recurrence_note)
    from ..data import get_dataset
    from ..data.batching import forecast_windows
    from ..models.seq2seq import Seq2SeqConfig, forecast, init_seq2seq, seq2seq_loss

    if args.stateful:
        raise SystemExit(
            "--stateful applies to contiguous-stream LM training only "
            "(forecast windows are independent)"
        )
    data = get_dataset("uci_electricity", args.data_path)
    if data["synthetic"]:
        logger.log({"note": "dataset uci_electricity: using synthetic stand-in"})
    context_len = args.seq_len or 168  # one week of hours
    horizon = 24
    # --use-pallas + --tensor-parallel is rejected centrally in cli.main()
    cfg = Seq2SeqConfig(
        num_features=data["num_features"],
        hidden_size=args.hidden_units,
        num_layers=args.num_layers,
        horizon=horizon,
        compute_dtype=args.compute_dtype,
        remat_chunk=args.remat_chunk,
        use_pallas=args.use_pallas,
        bptt=getattr(args, "bptt_mode", "sequential"),
    )

    def loss_fn(params, batch, dropout_rng):
        return seq2seq_loss(params, batch, cfg)

    key = jax.random.PRNGKey(args.seed)
    kp, kr = jax.random.split(key)
    params = init_seq2seq(kp, cfg)
    from ..cli import make_cli_optimizer
    optimizer = make_cli_optimizer(args)

    train_series, valid_series = data["train"], data["valid"]
    n_windows = max(len(train_series) - context_len - horizon + 1, 0)
    if n_windows < args.batch_size:
        raise SystemExit(
            f"train series too short: {n_windows} windows < batch {args.batch_size}"
        )
    steps_per_epoch = max(n_windows // args.batch_size, 1)

    fused_eval = bool(getattr(args, "fused_eval", False))
    if fused_eval and len(valid_series) < context_len + horizon:
        logger.log({"note": "fused-eval: valid series shorter than one "
                            "window; falling back to host-driven eval"})
        fused_eval = False
    if fused_eval:
        # Fused in-executable eval (works with BOTH feeds — device-data and
        # host-fed — and with --tensor-parallel): the free-running forecast
        # and its masked MSE/MAE sums run over the stacked host eval batches
        # (same `eval_batches` constructor as eval_fn, so the two paths can
        # never see different batches).
        import jax.numpy as jnp

        def metric_fn(p, b):
            preds = forecast(p, b["context"], cfg)
            w = b["valid"].astype(jnp.float32)
            n = jnp.maximum(w.sum(), 1.0)
            err = (preds - b["targets"]) * w[:, None, None]
            per_elem = float(horizon * preds.shape[-1])
            mse = (err ** 2).sum() / (n * per_elem)
            mae = jnp.abs(err).sum() / (n * per_elem)
            return {"eval_mse": mse, "eval_mae": mae}, w.sum()

        metric_keys = ("eval_mse", "eval_mae")
    else:
        metric_fn, metric_keys = None, ()

    if max(args.seq_parallel, args.pipeline_stages) > 1:
        raise SystemExit("--seq-parallel/--pipeline-stages apply to the LM "
                         "task; the forecaster supports --tensor-parallel")
    if args.tensor_parallel > 1:
        # metric_fn threads through so the (possibly fused) TP step is
        # built exactly ONCE
        from ..cli import _setup_tp_training
        from ..parallel.tensor_parallel import seq2seq_param_specs

        state, train_step, mesh, shards, wrap_stream, checkpoint_fn = (
            _setup_tp_training(
                args, logger, loss_fn=loss_fn, params=params,
                optimizer=optimizer, rng=kr,
                specs_fn=seq2seq_param_specs, hidden=cfg.hidden_size,
                metric_fn=metric_fn, metric_keys=metric_keys,
            )
        )
    else:
        state, train_step, mesh, shards, wrap_stream, checkpoint_fn = (
            _setup_training(
                args, logger, loss_fn=loss_fn, params=params,
                optimizer=optimizer, rng=kr,
            )
        )

    # data-exact resume: epoch seeds and in-epoch offsets follow the
    # restored step (same contract as the classifier runner)
    start_step = int(state.step)

    from ..data.batching import cap_batches

    def eval_batches(eval_quantum: int = 1):
        """THE eval-batch constructor shared by the host eval_fn and the
        fused-eval staging — one source, so the two paths can never see
        different batches. ``eval_quantum`` keeps the static batch shape a
        multiple of the TP data axis (host AND fused eval under
        --tensor-parallel both pass mesh.shape['data'])."""
        eval_bs = min(args.batch_size, 64)
        eval_bs = max(eval_bs - eval_bs % eval_quantum, eval_quantum)
        return cap_batches(
            forecast_windows(valid_series, context_len, horizon, eval_bs,
                             drop_remainder=False),
            getattr(args, "eval_batches", None),
        )

    # TP eval shards contexts over "data": the static batch shape must be a
    # multiple of the axis — ONE quantum shared by host eval_fn and the
    # fused-eval staging
    eval_quantum = mesh.shape["data"] if args.tensor_parallel > 1 else 1
    if fused_eval:
        from ..data import stage_stacked_batches

        ev_stacked = stage_stacked_batches(eval_batches(eval_quantum),
                                           mesh=mesh)

    if getattr(args, "device_data", False):
        # HBM-staged series; (context, horizon) windows sliced on-device from
        # per-step start indices — same shuffled order as forecast_windows,
        # so host-fed and device-resident runs see identical batches.
        import functools

        from ..data import slice_forecast_batch, stage_series
        from ..train import make_device_dp_train_step, make_device_train_step

        if args.prefetch:
            raise SystemExit("--device-data has no host feed; drop --prefetch")
        k = args.steps_per_call
        staged = stage_series(train_series, context_len, horizon, mesh=mesh)
        window_fn = functools.partial(
            slice_forecast_batch, context_len=context_len, horizon=horizon
        )
        from jax.sharding import PartitionSpec as P

        if mesh is None:
            dstep = make_device_train_step(
                loss_fn, optimizer, window_fn, metric_fn=metric_fn,
                metric_keys=metric_keys, grad_accum=args.grad_accum,
            )
        else:
            dstep = make_device_dp_train_step(
                loss_fn, optimizer, window_fn, mesh, {"series": P()},
                metric_fn=metric_fn, metric_keys=metric_keys,
                idx_spec=P(None, "data"), grad_accum=args.grad_accum,
            )
        if fused_eval:
            train_step = lambda state, idxs, do_eval: dstep(  # noqa: E731
                state, staged.arrays, idxs, ev_stacked, do_eval
            )
        else:
            train_step = lambda state, idxs: dstep(state, staged.arrays, idxs)  # noqa: E731

        from ..data.batching import forecast_starts, index_groups

        stream = index_groups(
            lambda epoch: forecast_starts(
                staged.num_windows, shuffle_seed=args.seed + epoch
            ),
            args.batch_size, k, start_step=start_step,
        )
    else:
        from ..data.batching import epoch_stream

        raw = epoch_stream(
            lambda epoch: forecast_windows(
                train_series, context_len, horizon, args.batch_size,
                shuffle_seed=args.seed + epoch,
            ),
            steps_per_epoch=steps_per_epoch, start_step=start_step,
        )
        if fused_eval and args.tensor_parallel > 1:
            # the TP step from _setup_tp_training already carries the gated
            # eval tail (uniform cond in a pure GSPMD jit program — no
            # manual-axis collectives to diverge on); bind its eval operand
            tstep = train_step
            train_step = lambda state, b, do_eval: tstep(  # noqa: E731
                state, b, ev_stacked, do_eval
            )
            stream = wrap_stream(raw)
        elif fused_eval:
            # host-fed feed + fused in-executable eval
            from ..train import make_dp_multi_train_step, make_multi_train_step

            if mesh is None:
                mstep = make_multi_train_step(
                    loss_fn, optimizer, metric_fn=metric_fn,
                    metric_keys=metric_keys, grad_accum=args.grad_accum,
                )
            else:
                mstep = make_dp_multi_train_step(
                    loss_fn, optimizer, mesh, metric_fn=metric_fn,
                    metric_keys=metric_keys, grad_accum=args.grad_accum,
                )
            train_step = lambda state, b, do_eval: mstep(  # noqa: E731
                state, b, ev_stacked, do_eval
            )
            stream = wrap_stream(raw, always_stack=True)
        else:
            stream = wrap_stream(raw)
    if args.tensor_parallel > 1:
        # eval on the DEVICE-RESIDENT sharded params — no host gather
        # (VERDICT r2 weak #6); contexts shard over the data axis
        from ..parallel.tensor_parallel import (
            make_tp_eval_step, seq2seq_param_specs,
        )

        fc = make_tp_eval_step(
            lambda p, ctx: forecast(p, ctx, cfg), mesh,
            seq2seq_param_specs(params),
        )
    else:
        fc = jax.jit(lambda p, ctx: forecast(p, ctx, cfg))

    def eval_fn(params):
        """Free-running (no teacher forcing) MSE/MAE over the valid tail,
        weighted by valid rows (filler rows in the last batch excluded)."""
        if len(valid_series) < context_len + horizon:
            return {"eval_skipped": 1}
        tot_n = tot_mse = tot_mae = 0.0
        for b in eval_batches(eval_quantum):
            preds = np.asarray(fc(params, b["context"]))
            err = (preds - b["targets"])[b["valid"]]
            n = b["valid"].sum()
            tot_mse += float((err**2).mean()) * n
            tot_mae += float(np.abs(err).mean()) * n
            tot_n += n
        tot_n = max(tot_n, 1.0)
        return {"eval_mse": tot_mse / tot_n, "eval_mae": tot_mae / tot_n}

    logger.log({
        "note": "start", "dataset": "uci_electricity",
        "features": data["num_features"], "context": context_len,
        "horizon": horizon, "devices": jax.device_count(), "partitions": shards,
        "steps_per_epoch": steps_per_epoch,
        "backend": "dp" if mesh is not None else "single",
        "recurrence": recurrence_note(
            args, cfg, shards, context_len,
            [cfg.num_features] + [cfg.hidden_size] * (cfg.num_layers - 1)),
    })
    from ..utils.flops import seq2seq_fwd_flops_per_seq

    # tokens_per_batch counts context positions; spread the per-sequence
    # FLOPs (encoder + decoder + projection) over them so
    # tokens/sec x flops_per_token = sequences/sec x flops_per_seq
    flops_per_token, peak = _mfu_logging(
        args,
        seq2seq_fwd_flops_per_seq(cfg.num_features, cfg.hidden_size,
                                  cfg.num_layers, context_len,
                                  horizon) / context_len,
        mesh, logger,
    )
    state = _make_logged_loop(
        args, state, train_step, stream, steps_per_epoch, logger,
        eval_fn=None if fused_eval else (eval_fn if args.eval_every else None),
        checkpoint_fn=checkpoint_fn,
        tokens_per_batch=args.batch_size * context_len,
        fused_eval=(lambda ms: {"eval_mse": float(ms["eval_mse"]),
                                "eval_mae": float(ms["eval_mae"])})
        if fused_eval else None,
        flops_per_token=flops_per_token,
        peak_tflops=peak,
        best_metric="eval_mse", best_mode="min",
    )
    # final eval on the device-resident params (TP: sharded in place; DP:
    # replicated) — no host round-trip of the model
    final = eval_fn(state.params)
    logger.log({"step": int(state.step), **final, "note": "final"})
    return 0
