"""Tensor parallelism for the non-LM models (classifier, seq2seq) and
dropout under the SP wavefront — VERDICT r1 "widen the parallelism
envelope" items. Parity oracle: the single-device train step."""

import jax
import numpy as np

from lstm_tensorspark_tpu.parallel import make_mesh
from lstm_tensorspark_tpu.parallel.tensor_parallel import (
    classifier_param_specs,
    make_tp_train_step,
    place_params,
    seq2seq_param_specs,
)
from lstm_tensorspark_tpu.train import make_optimizer, make_train_step
from lstm_tensorspark_tpu.train.loop import init_train_state


def _run(loss_fn, params, batches, opt, *, tp_specs=None, mesh=None):
    if tp_specs is None:
        step = make_train_step(loss_fn, opt)
        # a host copy: the step donates its state, and the caller's
        # ``params`` also start the sharded run this one is compared with
        s = init_train_state(jax.device_get(params), opt, jax.random.PRNGKey(1))
    else:
        step = make_tp_train_step(loss_fn, opt, mesh, params,
                                  param_specs=tp_specs, donate=False)
        placed = place_params(params, tp_specs, mesh)
        s = init_train_state(placed, opt, jax.random.PRNGKey(1))
    losses = []
    for b in batches:
        s, m = step(s, b)
        losses.append(float(m["loss"]))
    return s, losses


def test_tp_classifier_matches_single_device():
    from lstm_tensorspark_tpu.models import (
        ClassifierConfig, classifier_loss, init_classifier,
    )

    V, H, B, T = 13, 16, 8, 12
    cfg = ClassifierConfig(vocab_size=V, hidden_size=H, num_layers=2)
    params = init_classifier(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("sgd", 0.3)
    rng = np.random.RandomState(0)
    batches = [
        {
            "tokens": rng.randint(0, V, (B, T)).astype(np.int32),
            "lengths": rng.randint(3, T + 1, (B,)).astype(np.int32),
            "labels": rng.randint(0, 2, (B,)).astype(np.int32),
            "valid": np.ones((B,), np.float32),
        }
        for _ in range(3)
    ]

    def loss_fn(p, b, r):
        return classifier_loss(p, b, cfg)

    mesh = make_mesh(dp=4, tp=2)
    s0, want = _run(loss_fn, params, batches, opt)
    s1, got = _run(loss_fn, params, batches, opt,
                   tp_specs=classifier_param_specs(params), mesh=mesh)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-5
        ),
        jax.device_get(s1.params), jax.device_get(s0.params),
    )


def test_tp_seq2seq_matches_single_device():
    from lstm_tensorspark_tpu.models import (
        Seq2SeqConfig, init_seq2seq, seq2seq_loss,
    )

    F, H, B, T, HOR = 5, 16, 8, 12, 4
    cfg = Seq2SeqConfig(num_features=F, hidden_size=H, num_layers=2,
                        horizon=HOR)
    params = init_seq2seq(jax.random.PRNGKey(2), cfg)
    opt = make_optimizer("adam", 1e-2)
    rng = np.random.RandomState(1)
    batches = [
        {
            "context": rng.randn(B, T, F).astype(np.float32),
            "targets": rng.randn(B, HOR, F).astype(np.float32),
        }
        for _ in range(3)
    ]

    def loss_fn(p, b, r):
        return seq2seq_loss(p, b, cfg)

    mesh = make_mesh(dp=2, tp=4)
    _, want = _run(loss_fn, params, batches, opt)
    _, got = _run(loss_fn, params, batches, opt,
                  tp_specs=seq2seq_param_specs(params), mesh=mesh)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_sp_dropout_runs():
    """Dropout under the SP wavefront: finite losses, trajectory differs
    from deterministic (per-shard masks are live)."""
    from lstm_tensorspark_tpu.models import LMConfig, init_lm
    from lstm_tensorspark_tpu.parallel.train_step import (
        make_sharded_lm_train_step,
    )
    from lstm_tensorspark_tpu.parallel.tensor_parallel import place_lm_params

    V, H, B, T = 11, 16, 8, 16
    rng = np.random.RandomState(2)
    batches = [
        {
            "inputs": rng.randint(0, V, (B, T)).astype(np.int32),
            "targets": rng.randint(0, V, (B, T)).astype(np.int32),
        }
        for _ in range(4)
    ]
    opt = make_optimizer("sgd", 0.3)
    losses = {}
    for rate in (0.0, 0.5):
        cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2, dropout=rate)
        params = init_lm(jax.random.PRNGKey(3), cfg)
        mesh = make_mesh(dp=2, tp=2, sp=2)
        step = make_sharded_lm_train_step(cfg, opt, mesh, params,
                                          microbatches=2, donate=False)
        s = init_train_state(place_lm_params(params, mesh), opt,
                             jax.random.PRNGKey(4))
        ls = []
        for b in batches:
            s, m = step(s, b)
            ls.append(float(m["loss"]))
        assert np.isfinite(ls).all()
        losses[rate] = ls
    assert not np.allclose(losses[0.0], losses[0.5])


def test_sharded_eval_matches_single_device():
    """TP/SP sharded eval (no host gather): loss parity with lm_loss and a
    global token count for exact token weighting."""
    from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
    from lstm_tensorspark_tpu.parallel.tensor_parallel import place_lm_params
    from lstm_tensorspark_tpu.parallel.train_step import (
        make_sharded_lm_eval_step,
    )

    V, H, B, T = 11, 16, 8, 16
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2)
    params = init_lm(jax.random.PRNGKey(5), cfg)
    mesh = make_mesh(dp=2, tp=2, sp=2)
    placed = place_lm_params(params, mesh)
    ev = make_sharded_lm_eval_step(cfg, mesh, params, microbatches=2)
    rng = np.random.RandomState(6)
    b = {
        "inputs": rng.randint(0, V, (B, T)).astype(np.int32),
        "targets": rng.randint(0, V, (B, T)).astype(np.int32),
    }
    m = ev(placed, b)
    want, _ = lm_loss(params, b, cfg)
    np.testing.assert_allclose(float(m["loss"]), float(want), rtol=1e-5)
    assert float(m["tokens"]) == B * T


def test_tp_classifier_eval_on_sharded_params():
    """make_tp_eval_step: eval metrics computed on the device-resident
    TP-sharded params match the plain single-device eval (VERDICT r2
    weak #6 — no host gather)."""
    from lstm_tensorspark_tpu.models import (
        ClassifierConfig, classifier_loss, init_classifier,
    )
    from lstm_tensorspark_tpu.parallel.tensor_parallel import make_tp_eval_step

    V, H, B, T = 13, 16, 8, 12
    cfg = ClassifierConfig(vocab_size=V, hidden_size=H, num_layers=1)
    params = init_classifier(jax.random.PRNGKey(7), cfg)
    mesh = make_mesh(dp=4, tp=2)
    specs = classifier_param_specs(params)
    placed = place_params(params, specs, mesh)
    ev = make_tp_eval_step(lambda p, b: classifier_loss(p, b, cfg)[1],
                           mesh, specs)
    rng = np.random.RandomState(8)
    b = {
        "tokens": rng.randint(0, V, (B, T)).astype(np.int32),
        "lengths": rng.randint(3, T + 1, (B,)).astype(np.int32),
        "labels": rng.randint(0, 2, (B,)).astype(np.int32),
        "valid": np.ones((B,), np.float32),
    }
    got = ev(placed, b)
    want = classifier_loss(params, b, cfg)[1]
    np.testing.assert_allclose(float(got["loss"]), float(want["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(got["accuracy"]),
                               float(want["accuracy"]), rtol=1e-6)


def test_tp_seq2seq_eval_on_sharded_params():
    """Free-running forecast on TP-sharded params matches single-device."""
    from lstm_tensorspark_tpu.models import (
        Seq2SeqConfig, forecast, init_seq2seq,
    )
    from lstm_tensorspark_tpu.parallel.tensor_parallel import make_tp_eval_step

    F, H, B, T = 5, 16, 8, 12
    cfg = Seq2SeqConfig(num_features=F, hidden_size=H, num_layers=2, horizon=4)
    params = init_seq2seq(jax.random.PRNGKey(9), cfg)
    mesh = make_mesh(dp=2, tp=4)
    specs = seq2seq_param_specs(params)
    placed = place_params(params, specs, mesh)
    fc = make_tp_eval_step(lambda p, ctx: forecast(p, ctx, cfg), mesh, specs)
    ctx = np.random.RandomState(10).randn(B, T, F).astype(np.float32)
    got = np.asarray(fc(placed, ctx))
    want = np.asarray(forecast(params, ctx, cfg))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
