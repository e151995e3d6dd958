"""IMDB-style bi-LSTM classification task runner (BASELINE.md config 2).

Wires the bi-LSTM classifier (models/classifier.py) into the CLI: epochs of
bucketed padded batches, single-chip or data-parallel training (via the
shared cli._setup_training orchestration, including checkpoint/resume),
accuracy eval. Evaluation runs single-device on the small held-out split
(params are replicated, so any device's copy works).
"""

from __future__ import annotations

import jax


def run_classifier(args, logger) -> int:
    from ..cli import (_make_logged_loop, _mfu_logging, _setup_training,
                       recurrence_note)
    from ..data import get_dataset, padded_batches
    from ..models.classifier import ClassifierConfig, classifier_loss, init_classifier

    if args.stateful:
        raise SystemExit(
            "--stateful applies to contiguous-stream LM training only "
            "(classification examples are independent)"
        )
    max_len = args.seq_len or 400  # config-2 default
    data = get_dataset("imdb", args.data_path, max_len=max_len)
    if data["synthetic"]:
        logger.log({"note": "dataset imdb: using synthetic stand-in"})
    vocab = data["vocab"]
    # --use-pallas + --tensor-parallel is rejected centrally in cli.main()
    cfg = ClassifierConfig(
        vocab_size=len(vocab),
        num_classes=data["num_classes"],
        hidden_size=args.hidden_units,
        num_layers=args.num_layers,
        dropout=args.dropout,
        compute_dtype=args.compute_dtype,
        remat_chunk=args.remat_chunk,
        use_pallas=args.use_pallas,
        bptt=getattr(args, "bptt_mode", "sequential"),
    )

    def loss_fn(params, batch, dropout_rng):
        return classifier_loss(
            params, batch, cfg,
            dropout_rng=dropout_rng,
            deterministic=dropout_rng is None or cfg.dropout == 0.0,
        )

    key = jax.random.PRNGKey(args.seed)
    kp, kr = jax.random.split(key)
    params = init_classifier(kp, cfg)
    from ..cli import make_cli_optimizer
    optimizer = make_cli_optimizer(args)

    train_seqs, train_labels = data["train"]
    valid_seqs, valid_labels = data["valid"]
    if len(train_seqs) < args.batch_size:
        raise SystemExit(
            f"train set too small: {len(train_seqs)} examples < batch {args.batch_size}"
        )
    steps_per_epoch = max(len(train_seqs) // args.batch_size, 1)

    fused_eval = bool(getattr(args, "fused_eval", False))
    if fused_eval and not valid_seqs:
        logger.log({"note": "fused-eval: empty valid split; "
                            "falling back to host-driven eval"})
        fused_eval = False
    if fused_eval:
        # Fused in-executable eval (works with BOTH feeds — device-data and
        # host-fed — and with --tensor-parallel): the weighted accuracy/loss
        # sums run over the stacked host eval batches (same `eval_batches`
        # constructor as eval_fn, so the two paths can never see different
        # batches).
        import numpy as np

        def metric_fn(p, b):
            _, aux = classifier_loss(p, b, cfg)
            w = b["valid"].astype(np.float32).sum()
            return ({"eval_loss": aux["loss"],
                     "eval_accuracy": aux["accuracy"]}, w)

        metric_keys = ("eval_loss", "eval_accuracy")
    else:
        metric_fn, metric_keys = None, ()

    if max(args.seq_parallel, args.pipeline_stages) > 1:
        raise SystemExit("--seq-parallel/--pipeline-stages apply to the LM "
                         "task; the classifier supports --tensor-parallel")
    if args.tensor_parallel > 1:
        # metric_fn threads through so the (possibly fused) TP step is
        # built exactly ONCE
        from ..cli import _setup_tp_training
        from ..parallel.tensor_parallel import classifier_param_specs

        state, train_step, mesh, shards, wrap_stream, checkpoint_fn = (
            _setup_tp_training(
                args, logger, loss_fn=loss_fn, params=params,
                optimizer=optimizer, rng=kr,
                specs_fn=classifier_param_specs, hidden=cfg.hidden_size,
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
    # restored step, so the resumed shuffle order matches the
    # uninterrupted run exactly
    start_step = int(state.step)

    from ..data.batching import cap_batches, padded_batches

    def eval_batches(eval_quantum: int = 1):
        """THE eval-batch constructor shared by the host eval_fn and the
        fused-eval staging — one source, so the two paths can never see
        different batches. ``eval_quantum`` keeps the static batch shape a
        multiple of the TP data axis (host AND fused eval under
        --tensor-parallel both pass mesh.shape['data'])."""
        eval_bs = min(args.batch_size, len(valid_seqs))
        eval_bs = max(eval_bs - eval_bs % eval_quantum, eval_quantum)
        return cap_batches(
            padded_batches(valid_seqs, valid_labels, eval_bs, max_len,
                           drop_remainder=False),
            getattr(args, "eval_batches", None),
        )

    # TP eval shards batch rows over "data": the static batch shape must be
    # a multiple of the axis — ONE quantum shared by host eval_fn and the
    # fused-eval staging
    eval_quantum = mesh.shape["data"] if args.tensor_parallel > 1 else 1
    if fused_eval:
        from ..data import stage_stacked_batches

        ev_stacked = stage_stacked_batches(eval_batches(eval_quantum),
                                           mesh=mesh)

    if getattr(args, "device_data", False):
        # HBM-staged padded example matrix; batches gathered on-device by
        # row indices in the same shuffle+bucket order as padded_batches.
        import numpy as np

        from ..data import stage_examples, take_batch
        from ..train import make_device_dp_train_step, make_device_train_step

        if args.prefetch:
            raise SystemExit("--device-data has no host feed; drop --prefetch")
        k = args.steps_per_call
        N = len(train_seqs)
        toks = np.zeros((N, max_len), np.int32)
        lens = np.zeros((N,), np.int32)
        for r, seq in enumerate(train_seqs):
            seq = seq[:max_len]
            toks[r, : len(seq)] = seq
            lens[r] = len(seq)
        staged = stage_examples(
            {
                "tokens": toks,
                "lengths": lens,
                "labels": np.asarray(train_labels, np.int32),
                "valid": np.ones((N,), bool),
            },
            mesh=mesh,
        )
        from jax.sharding import PartitionSpec as P

        arrays_spec = {k2: P() for k2 in staged.arrays}
        if mesh is None:
            dstep = make_device_train_step(
                loss_fn, optimizer, take_batch, metric_fn=metric_fn,
                metric_keys=metric_keys, grad_accum=args.grad_accum,
            )
        else:
            dstep = make_device_dp_train_step(
                loss_fn, optimizer, take_batch, mesh, arrays_spec,
                metric_fn=metric_fn, metric_keys=metric_keys,
                idx_spec=P(None, "data"), grad_accum=args.grad_accum,
            )
        if fused_eval:
            train_step = lambda state, idxs, do_eval: dstep(  # noqa: E731
                state, staged.arrays, idxs, ev_stacked, do_eval
            )
        else:
            train_step = lambda state, idxs: dstep(state, staged.arrays, idxs)  # noqa: E731

        from ..data.batching import example_order, index_groups

        lengths_all = [len(s) for s in train_seqs]
        stream = index_groups(
            lambda epoch: example_order(
                lengths_all, shuffle_seed=args.seed + epoch
            ),
            args.batch_size, k, start_step=start_step,
        )
    else:
        from ..data.batching import epoch_stream

        raw = epoch_stream(
            lambda epoch: padded_batches(
                train_seqs, train_labels, args.batch_size, max_len,
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
        # (VERDICT r2 weak #6); batches shard over the data axis
        from ..parallel.tensor_parallel import (
            classifier_param_specs, make_tp_eval_step,
        )

        eval_step = make_tp_eval_step(
            lambda p, b: classifier_loss(p, b, cfg)[1], mesh,
            classifier_param_specs(params),
        )
    else:
        eval_step = jax.jit(lambda p, b: classifier_loss(p, b, cfg)[1])

    def eval_fn(params):
        if not valid_seqs:
            return {"eval_skipped": 1}
        tot_w = tot_loss = tot_acc = 0.0
        for b in eval_batches(eval_quantum):
            m = eval_step(params, b)
            w = float(b["valid"].sum())
            tot_loss += float(m["loss"]) * w
            tot_acc += float(m["accuracy"]) * w
            tot_w += w
        tot_w = max(tot_w, 1.0)
        return {"eval_loss": tot_loss / tot_w, "eval_accuracy": tot_acc / tot_w}

    logger.log({
        "note": "start", "dataset": "imdb", "vocab": len(vocab),
        "max_len": max_len, "devices": jax.device_count(), "partitions": shards,
        "steps_per_epoch": steps_per_epoch,
        "backend": "dp" if mesh is not None else "single",
        "recurrence": recurrence_note(
            args, cfg, shards, max_len,
            [cfg.embed] + [2 * cfg.hidden_size] * (cfg.num_layers - 1),
            has_mask=True, bidir=True),
    })
    from ..utils.flops import classifier_fwd_flops_per_token

    flops_per_token, peak = _mfu_logging(
        args,
        classifier_fwd_flops_per_token(cfg.vocab_size, cfg.hidden_size,
                                       cfg.num_layers, cfg.embed),
        mesh, logger,
    )
    state = _make_logged_loop(
        args, state, train_step, stream, steps_per_epoch, logger,
        eval_fn=None if fused_eval else (eval_fn if args.eval_every else None),
        checkpoint_fn=checkpoint_fn,
        tokens_per_batch=args.batch_size * max_len,
        fused_eval=(lambda ms: {"eval_loss": float(ms["eval_loss"]),
                                "eval_accuracy": float(ms["eval_accuracy"])})
        if fused_eval else None,
        flops_per_token=flops_per_token,
        peak_tflops=peak,
        best_metric="eval_accuracy", best_mode="max",
    )
    # final eval on the device-resident params (TP: sharded in place; DP:
    # replicated) — no host round-trip of the model
    final = eval_fn(state.params)
    logger.log({"step": int(state.step), **final, "note": "final"})
    return 0
