"""Gradient accumulation: N microbatches must produce the full-batch update
exactly (equal microbatch sizes ⇒ mean-of-means == full mean), single-chip
and under DP; memory behavior is XLA's, but semantics are testable."""

import jax
import jax.numpy as jnp
import numpy as np

from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.parallel import make_dp_train_step, make_mesh, shard_batch
from lstm_tensorspark_tpu.parallel.data_parallel import replicate
from lstm_tensorspark_tpu.train import make_optimizer, make_train_step
from lstm_tensorspark_tpu.train.loop import init_train_state


def _setup(B=8, T=12, V=23, H=16):
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2)
    # a host copy: each test builds two states from it, and each step donates its own
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), cfg))
    opt = make_optimizer("sgd", 0.5)

    def loss_fn(p, batch, rng):
        return lm_loss(p, batch, cfg)

    rng = np.random.RandomState(0)
    batch = {
        "inputs": rng.randint(0, V, (B, T)).astype(np.int32),
        "targets": rng.randint(0, V, (B, T)).astype(np.int32),
    }
    return cfg, params, opt, loss_fn, batch


def test_accum_matches_full_batch_single_chip():
    cfg, params, opt, loss_fn, batch = _setup()
    s_full = init_train_state(params, opt, jax.random.PRNGKey(1))
    s_acc = init_train_state(params, opt, jax.random.PRNGKey(1))
    full = make_train_step(loss_fn, opt, jit=True)
    acc = make_train_step(loss_fn, opt, jit=True, grad_accum=4)
    s_full, m_full = full(s_full, batch)
    s_acc, m_acc = acc(s_acc, batch)
    np.testing.assert_allclose(
        float(m_full["loss"]), float(m_acc["loss"]), rtol=1e-6
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6),
        jax.device_get(s_full.params), jax.device_get(s_acc.params),
    )


def test_accum_matches_full_batch_dp():
    cfg, params, opt, loss_fn, batch = _setup(B=16)
    mesh = make_mesh(dp=4, devices=np.asarray(jax.devices()[:4]))
    full = make_dp_train_step(loss_fn, opt, mesh)
    acc = make_dp_train_step(loss_fn, opt, mesh, grad_accum=2)
    sb = shard_batch(batch, mesh)
    s0 = init_train_state(replicate(params, mesh), opt, jax.random.PRNGKey(1))
    s_full, m_full = full(s0, sb)
    s0 = init_train_state(replicate(params, mesh), opt, jax.random.PRNGKey(1))
    s_acc, m_acc = acc(s0, sb)
    np.testing.assert_allclose(
        float(m_full["loss"]), float(m_acc["loss"]), rtol=1e-6
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6),
        jax.device_get(s_full.params), jax.device_get(s_acc.params),
    )


def test_accum_multiple_steps_trains():
    """Loss decreases over a few accumulated steps (the path is trainable)."""
    cfg, params, opt, loss_fn, batch = _setup()
    step = make_train_step(loss_fn, opt, grad_accum=2)
    s = init_train_state(params, opt, jax.random.PRNGKey(1))
    losses = []
    for _ in range(8):
        s, m = step(s, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_cli_rejects_bad_accum(tmp_path):
    import pytest

    from lstm_tensorspark_tpu.cli import main

    with pytest.raises(SystemExit):
        main([
            "--dataset", "ptb_char", "--batch-size", "8", "--num-steps", "1",
            "--backend", "single", "--grad-accum", "3",  # 8 % 3 != 0
        ])
    with pytest.raises(SystemExit):
        main([
            "--dataset", "ptb_char", "--batch-size", "8", "--num-steps", "1",
            "--backend", "single", "--grad-accum", "2", "--stateful",
        ])


def test_cli_accum_end_to_end(tmp_path):
    import json

    from lstm_tensorspark_tpu.cli import main

    jsonl = tmp_path / "m.jsonl"
    rc = main([
        "--dataset", "ptb_char", "--hidden-units", "32", "--batch-size", "8",
        "--num-steps", "4", "--log-every", "2", "--grad-accum", "2",
        "--num-partitions", "2", "--jsonl", str(jsonl),
    ])
    assert rc == 0
    records = [json.loads(l) for l in jsonl.read_text().splitlines()]
    assert any("loss" in r for r in records)
