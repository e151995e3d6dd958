"""Data-parallel backend tests on the virtual 8-device CPU mesh — the
`local[N]` equivalent (SURVEY.md §4): 1-device vs N-device loss parity at
equal global batch, the reference's synchronous grad-averaging semantics
(SURVEY.md §3.3)."""

import jax
import jax.numpy as jnp
import numpy as np

from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.parallel import (
    make_dp_eval_step,
    make_dp_train_step,
    make_mesh,
    shard_batch,
)
from lstm_tensorspark_tpu.parallel.data_parallel import replicate
from lstm_tensorspark_tpu.train import make_optimizer, make_train_step
from lstm_tensorspark_tpu.train.loop import init_train_state

V, H, B, T = 11, 16, 8, 12


def _setup():
    cfg = LMConfig(vocab_size=V, hidden_size=H)

    def loss_fn(params, batch, rng):
        return lm_loss(params, batch, cfg)

    opt = make_optimizer("sgd", 0.3)
    # a host copy: the runs of one test start from it, each donating its own
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), cfg))
    rng = np.random.RandomState(0)
    batches = [
        {
            "inputs": rng.randint(0, V, (B, T)).astype(np.int32),
            "targets": rng.randint(0, V, (B, T)).astype(np.int32),
        }
        for _ in range(5)
    ]
    return cfg, loss_fn, opt, params, batches


def test_dp_matches_single_device():
    cfg, loss_fn, opt, params, batches = _setup()

    single = make_train_step(loss_fn, opt)
    s1 = init_train_state(params, opt, jax.random.PRNGKey(1))
    losses1 = []
    for b in batches:
        s1, m = single(s1, b)
        losses1.append(float(m["loss"]))

    mesh = make_mesh(dp=8)
    dp = make_dp_train_step(loss_fn, opt, mesh)
    s2 = init_train_state(params, opt, jax.random.PRNGKey(1))
    s2 = s2._replace(params=replicate(s2.params, mesh),
                     opt_state=replicate(s2.opt_state, mesh))
    losses2 = []
    for b in batches:
        s2, m = dp(s2, shard_batch(b, mesh))
        losses2.append(float(m["loss"]))

    np.testing.assert_allclose(losses1, losses2, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        jax.device_get(s1.params),
        jax.device_get(s2.params),
    )


def test_dp_eval_matches_single():
    cfg, loss_fn, opt, params, batches = _setup()
    mesh = make_mesh(dp=8)
    ev = make_dp_eval_step(loss_fn, mesh)
    p = replicate(params, mesh)
    got = float(ev(p, shard_batch(batches[0], mesh))["loss"])
    want = float(loss_fn(params, batches[0], None)[0])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_dp_smaller_mesh():
    """--num-partitions < device count: a 4-device data axis also works."""
    cfg, loss_fn, opt, params, batches = _setup()
    mesh = make_mesh(dp=4, devices=np.asarray(jax.devices()[:4]))
    dp = make_dp_train_step(loss_fn, opt, mesh)
    s = init_train_state(params, opt, jax.random.PRNGKey(1))
    s = s._replace(params=replicate(s.params, mesh),
                   opt_state=replicate(s.opt_state, mesh))
    s, m = dp(s, shard_batch(batches[0], mesh))
    assert np.isfinite(float(m["loss"]))


def test_stateful_dp_matches_single():
    """Stateful TBPTT: carries thread across windows identically on the
    single-chip and DP paths (carries sharded over the data axis)."""
    cfg = LMConfig(vocab_size=V, hidden_size=H)
    from lstm_tensorspark_tpu.models.lstm_lm import init_carries

    def loss_fn(params, batch, rng, carries):
        return lm_loss(params, batch, cfg, carries=carries)

    opt = make_optimizer("sgd", 0.3)
    # a host copy: the runs of one test start from it, each donating its own
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), cfg))
    rng = np.random.RandomState(0)
    batches = [
        {
            "inputs": rng.randint(0, V, (B, T)).astype(np.int32),
            "targets": rng.randint(0, V, (B, T)).astype(np.int32),
        }
        for _ in range(4)
    ]

    single = make_train_step(loss_fn, opt, stateful=True)
    s1 = init_train_state(params, opt, jax.random.PRNGKey(1),
                          carries=init_carries(cfg, B))
    losses1 = []
    for b in batches:
        s1, m = single(s1, b)
        losses1.append(float(m["loss"]))
    # carries actually moved away from zero
    assert float(jnp.abs(s1.carries[0][0]).max()) > 0

    mesh = make_mesh(dp=8)
    dp = make_dp_train_step(loss_fn, opt, mesh, stateful=True)
    s2 = init_train_state(params, opt, jax.random.PRNGKey(1),
                          carries=init_carries(cfg, B))
    s2 = s2._replace(params=replicate(s2.params, mesh),
                     opt_state=replicate(s2.opt_state, mesh),
                     carries=shard_batch(s2.carries, mesh))
    losses2 = []
    for b in batches:
        s2, m = dp(s2, shard_batch(b, mesh))
        losses2.append(float(m["loss"]))
    np.testing.assert_allclose(losses1, losses2, rtol=1e-5, atol=1e-6)

    # stateful must differ from stateless after the first window
    def loss_fn_sl(params, batch, rng):
        return lm_loss(params, batch, cfg)
    stateless = make_train_step(loss_fn_sl, opt)
    s3 = init_train_state(params, opt, jax.random.PRNGKey(1))
    sl_losses = []
    for b in batches:
        s3, m = stateless(s3, b)
        sl_losses.append(float(m["loss"]))
    assert abs(sl_losses[1] - losses1[1]) > 1e-8
