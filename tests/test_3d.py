"""Combined 3D parallelism (DP x TP x SP on one mesh): exact loss parity
with the single-device step over several steps."""

import jax
import numpy as np

from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.parallel import make_mesh
from lstm_tensorspark_tpu.parallel.tensor_parallel import place_lm_params
from lstm_tensorspark_tpu.parallel.train_step import make_sharded_lm_train_step
from lstm_tensorspark_tpu.train import make_optimizer, make_train_step
from lstm_tensorspark_tpu.train.loop import init_train_state

V, H, B, T = 11, 16, 8, 16


def test_dp_tp_sp_matches_single_device():
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2)

    def loss_fn(p, b, r):
        return lm_loss(p, b, cfg)

    opt = make_optimizer("sgd", 0.3)
    # a host copy: both runs start from it, and the first step donates its state
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), cfg))
    rngb = np.random.RandomState(0)
    batches = [
        {
            "inputs": rngb.randint(0, V, (B, T)).astype(np.int32),
            "targets": rngb.randint(0, V, (B, T)).astype(np.int32),
        }
        for _ in range(3)
    ]

    step0 = make_train_step(loss_fn, opt)
    s0 = init_train_state(params, opt, jax.random.PRNGKey(1))
    want = []
    for b in batches:
        s0, m = step0(s0, b)
        want.append(float(m["loss"]))

    mesh = make_mesh(dp=2, tp=2, sp=2)
    placed = place_lm_params(params, mesh)
    step3 = make_sharded_lm_train_step(cfg, opt, mesh, params,
                                       microbatches=2, donate=False)
    s3 = init_train_state(placed, opt, jax.random.PRNGKey(1))
    got = []
    for b in batches:
        s3, m = step3(s3, b)
        got.append(float(m["loss"]))

    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # params updated identically
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-5
        ),
        jax.device_get(s0.params), jax.device_get(s3.params),
    )


def test_dp_tp_sp_matches_single_device_bf16_logits():
    """logits_dtype="bfloat16" under DP x TP x SP: the head matmul's
    partial products round to bf16 on each model shard BEFORE the GSPMD
    psum (vs add-then-round unsharded), so the law here is
    tolerance-close, not bit-equal — loss within bf16 rounding of the
    single-device bf16-logits run, and training stays finite and aligned
    over steps."""
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2,
                   logits_dtype="bfloat16")

    def loss_fn(p, b, r):
        return lm_loss(p, b, cfg)

    opt = make_optimizer("sgd", 0.3)
    params = jax.device_get(init_lm(jax.random.PRNGKey(2), cfg))
    rngb = np.random.RandomState(1)
    batches = [
        {
            "inputs": rngb.randint(0, V, (B, T)).astype(np.int32),
            "targets": rngb.randint(0, V, (B, T)).astype(np.int32),
        }
        for _ in range(3)
    ]

    step0 = make_train_step(loss_fn, opt)
    s0 = init_train_state(params, opt, jax.random.PRNGKey(1))
    want = []
    for b in batches:
        s0, m = step0(s0, b)
        want.append(float(m["loss"]))

    mesh = make_mesh(dp=2, tp=2, sp=2)
    placed = place_lm_params(params, mesh)
    step3 = make_sharded_lm_train_step(cfg, opt, mesh, params,
                                       microbatches=2, donate=False)
    s3 = init_train_state(placed, opt, jax.random.PRNGKey(1))
    got = []
    for b in batches:
        s3, m = step3(s3, b)
        got.append(float(m["loss"]))

    assert np.isfinite(got).all()
    # bf16 rounding of sharded partials: ~3 decimal digits of agreement
    np.testing.assert_allclose(got, want, rtol=5e-3)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=2e-2, atol=2e-3
        ),
        jax.device_get(s0.params), jax.device_get(s3.params),
    )
