"""Tensor-parallel (GSPMD-sharded) LM training: loss parity with the
single-device step under pure TP and combined DP x TP meshes, and sharded
parameter placement."""

import jax
import jax.numpy as jnp
import numpy as np

from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.parallel import make_mesh
from lstm_tensorspark_tpu.parallel.tensor_parallel import (
    make_tp_train_step,
    place_lm_params,
)
from lstm_tensorspark_tpu.train import make_optimizer, make_train_step
from lstm_tensorspark_tpu.train.loop import init_train_state

V, H, B, T = 11, 16, 8, 12


def _setup(num_layers=2):
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=num_layers)

    def loss_fn(params, batch, rng):
        return lm_loss(params, batch, cfg)

    opt = make_optimizer("sgd", 0.3)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    batches = [
        {
            "inputs": rng.randint(0, V, (B, T)).astype(np.int32),
            "targets": rng.randint(0, V, (B, T)).astype(np.int32),
        }
        for _ in range(4)
    ]
    return cfg, loss_fn, opt, params, batches


def _single_losses(loss_fn, opt, params, batches):
    step = make_train_step(loss_fn, opt)
    # a host copy: the step donates its state, and the caller's ``params``
    # also start the tensor-parallel run this one is compared with
    s = init_train_state(jax.device_get(params), opt, jax.random.PRNGKey(1))
    out = []
    for b in batches:
        s, m = step(s, b)
        out.append(float(m["loss"]))
    return out, s


def _tp_losses(mesh, loss_fn, opt, params, batches):
    placed = place_lm_params(params, mesh)
    step = make_tp_train_step(loss_fn, opt, mesh, params, donate=False)
    s = init_train_state(placed, opt, jax.random.PRNGKey(1))
    out = []
    for b in batches:
        s, m = step(s, b)
        out.append(float(m["loss"]))
    return out, s


def test_params_actually_sharded():
    cfg, loss_fn, opt, params, batches = _setup()
    mesh = make_mesh(dp=1, tp=8, sp=1)
    placed = place_lm_params(params, mesh)
    W = placed["layers"][0].W_i  # [D, H] column-sharded into H/8
    shard_shapes = {s.data.shape for s in W.addressable_shards}
    assert shard_shapes == {(H, H // 8)} or shard_shapes == {(W.shape[0], H // 8)}
    emb = placed["embedding"]
    assert all(s.data.shape == emb.shape for s in emb.addressable_shards)


def test_tp_matches_single_device():
    cfg, loss_fn, opt, params, batches = _setup()
    want, s_ref = _single_losses(loss_fn, opt, params, batches)
    mesh = make_mesh(dp=1, tp=8, sp=1)
    got, s_tp = _tp_losses(mesh, loss_fn, opt, params, batches)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        jax.device_get(s_ref.params), jax.device_get(s_tp.params),
    )


def test_dp_tp_combined_matches_single_device():
    cfg, loss_fn, opt, params, batches = _setup()
    want, _ = _single_losses(loss_fn, opt, params, batches)
    mesh = make_mesh(dp=2, tp=4, sp=1)
    got, _ = _tp_losses(mesh, loss_fn, opt, params, batches)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
