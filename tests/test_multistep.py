"""K-steps-per-dispatch tests (train/multistep.py): the scanned K-step
program must be exactly K iterations of the shared single-step body — parity
against sequential single steps, single-chip and DP, stateless and stateful,
plus the host-side stacking/prefetch feed and the CLI path."""

import jax
import jax.numpy as jnp
import numpy as np

from lstm_tensorspark_tpu.data import prefetch_to_device, stacked_batches
from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.models.lstm_lm import init_carries
from lstm_tensorspark_tpu.parallel import make_mesh, shard_batch
from lstm_tensorspark_tpu.parallel.data_parallel import replicate
from lstm_tensorspark_tpu.train import (
    make_dp_multi_train_step,
    make_multi_train_step,
    make_optimizer,
    make_train_step,
)
from lstm_tensorspark_tpu.train.loop import init_train_state

V, H, B, T, K = 11, 16, 8, 12, 4


def _setup(stateful=False):
    cfg = LMConfig(vocab_size=V, hidden_size=H)

    if stateful:

        def loss_fn(params, batch, rng, carries):
            return lm_loss(params, batch, cfg, carries=carries)

    else:

        def loss_fn(params, batch, rng):
            return lm_loss(params, batch, cfg)

    opt = make_optimizer("momentum", 0.3, momentum=0.9)
    # a host copy: every run below places its own, and its step donates it
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), cfg))
    rng = np.random.RandomState(0)
    batches = [
        {
            "inputs": rng.randint(0, V, (B, T)).astype(np.int32),
            "targets": rng.randint(0, V, (B, T)).astype(np.int32),
        }
        for _ in range(K)
    ]
    stacked = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
    return cfg, loss_fn, opt, params, batches, stacked


def _tree_close(a, b, tol=1e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=tol, rtol=tol)


def test_multistep_matches_sequential_single_steps():
    cfg, loss_fn, opt, params, batches, stacked = _setup()

    single = make_train_step(loss_fn, opt)
    s1 = init_train_state(params, opt, jax.random.PRNGKey(1))
    losses = []
    for b in batches:
        s1, m = single(s1, b)
        losses.append(float(m["loss"]))

    multi = make_multi_train_step(loss_fn, opt)
    s2 = init_train_state(params, opt, jax.random.PRNGKey(1))
    s2, mm = multi(s2, stacked)

    assert int(s2.step) == K == int(s1.step)
    _tree_close(s1.params, s2.params)
    np.testing.assert_allclose(float(mm["loss"]), np.mean(losses), atol=1e-6)
    np.testing.assert_allclose(float(mm["loss_last"]), losses[-1], atol=1e-6)


def test_multistep_stateful_carries_thread_through_scan():
    cfg, loss_fn, opt, params, batches, stacked = _setup(stateful=True)

    single = make_train_step(loss_fn, opt, stateful=True)
    s1 = init_train_state(
        params, opt, jax.random.PRNGKey(1), carries=init_carries(cfg, B)
    )
    for b in batches:
        s1, _ = single(s1, b)

    multi = make_multi_train_step(loss_fn, opt, stateful=True)
    s2 = init_train_state(
        params, opt, jax.random.PRNGKey(1), carries=init_carries(cfg, B)
    )
    s2, _ = multi(s2, stacked)

    _tree_close(s1.params, s2.params)
    _tree_close(s1.carries, s2.carries)


def test_dp_multistep_matches_single_device_multistep():
    cfg, loss_fn, opt, params, batches, stacked = _setup()

    multi = make_multi_train_step(loss_fn, opt)
    s1 = init_train_state(params, opt, jax.random.PRNGKey(1))
    s1, m1 = multi(s1, stacked)

    mesh = make_mesh(dp=8)
    dp_multi = make_dp_multi_train_step(loss_fn, opt, mesh)
    s2 = init_train_state(params, opt, jax.random.PRNGKey(1))
    s2 = s2._replace(params=replicate(s2.params, mesh),
                     opt_state=replicate(s2.opt_state, mesh))
    s2, m2 = dp_multi(s2, shard_batch(stacked, mesh, dim=1))

    assert int(s2.step) == K
    _tree_close(s1.params, s2.params, tol=1e-5)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), atol=1e-5)


def test_stacked_batches_and_prefetch_feed():
    rng = np.random.RandomState(0)
    stream = (
        {"inputs": rng.randint(0, V, (B, T)).astype(np.int32)} for _ in range(7)
    )
    chunks = list(prefetch_to_device(stacked_batches(stream, 3)))
    assert len(chunks) == 2  # trailing partial group of 1 dropped
    assert chunks[0]["inputs"].shape == (3, B, T)
    assert isinstance(chunks[0]["inputs"], jax.Array)


def test_prefetch_abandoned_consumer_stops_producer():
    import threading
    import time

    produced = []

    def infinite():
        i = 0
        while True:
            produced.append(i)
            yield {"x": np.full((2,), i, np.float32)}
            i += 1

    it = prefetch_to_device(infinite(), size=2)
    next(it)
    it.close()  # abandon mid-stream → producer must quit, queue drain
    n_after_close = len(produced)
    time.sleep(0.2)
    # producer made no further progress beyond the item it may have been
    # blocked on when the consumer vanished
    assert len(produced) <= n_after_close + 1
    assert not any(
        t.is_alive() and t.daemon and "producer" in repr(t.name)
        for t in threading.enumerate()
        if t.name.startswith("prefetch")
    )


def test_prefetch_propagates_producer_errors():
    def bad():
        yield {"x": np.zeros((2,), np.float32)}
        raise ValueError("boom")

    it = prefetch_to_device(bad())
    next(it)
    try:
        next(it)
        assert False, "expected ValueError"
    except ValueError as e:
        assert "boom" in str(e)


def test_cli_steps_per_call_e2e(tmp_path):
    from lstm_tensorspark_tpu.cli import main

    jsonl = tmp_path / "m.jsonl"
    rc = main([
        "--dataset", "ptb_char", "--hidden-units", "16", "--batch-size", "8",
        "--seq-len", "16", "--num-steps", "8", "--steps-per-call", "4",
        "--log-every", "1", "--jsonl", str(jsonl), "--backend", "dp",
        "--num-partitions", "4",
    ])
    assert rc == 0
    import json

    records = [json.loads(l) for l in jsonl.read_text().splitlines()]
    steps = [r["step"] for r in records if "loss" in r and "step" in r]
    assert steps and steps[-1] == 8  # 2 calls x 4 steps
