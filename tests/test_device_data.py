"""Device-resident dataset (data/device_dataset.py + train/device_step.py):
window slicing must reproduce the host-fed stream exactly, and the K-step
device-data training must be bit-identical to host-fed training — single
chip and DP."""

import jax
import numpy as np

from lstm_tensorspark_tpu.data import (
    lm_batch_stream,
    slice_window,
    stacked_batches,
    stage_lm_data,
    window_index_stream,
)
from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.parallel import make_mesh, shard_batch
from lstm_tensorspark_tpu.parallel.data_parallel import replicate
from lstm_tensorspark_tpu.train import (
    make_device_dp_lm_train_step,
    make_device_lm_train_step,
    make_dp_multi_train_step,
    make_multi_train_step,
    make_optimizer,
)
from lstm_tensorspark_tpu.train.loop import init_train_state

B, T, V, H, K = 8, 16, 29, 16, 4


def _tokens(n=B * T * 12 + 1):
    return np.random.RandomState(0).randint(0, V, n).astype(np.int32)


def _cfg():
    return LMConfig(vocab_size=V, hidden_size=H, num_layers=2)


def test_slice_window_matches_host_stream():
    tokens = _tokens()
    data = stage_lm_data(tokens, B, T)
    host = list(lm_batch_stream(tokens, B, T, num_epochs=1))
    assert len(host) == data.n_windows
    for w, hb in enumerate(host):
        dev = jax.jit(lambda a, w: slice_window(a, w, T))(
            data.arrays, np.int32(w)
        )
        np.testing.assert_array_equal(np.asarray(dev["inputs"]), hb["inputs"])
        np.testing.assert_array_equal(np.asarray(dev["targets"]), hb["targets"])


def test_device_data_matches_host_fed_training():
    tokens = _tokens()
    cfg = _cfg()

    def loss_fn(p, b, r):
        return lm_loss(p, b, cfg)

    opt = make_optimizer("sgd", 0.3)
    # a host copy: both runs start from it, and each step donates its state
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), cfg))

    host_step = make_multi_train_step(loss_fn, opt)
    s_host = init_train_state(params, opt, jax.random.PRNGKey(1))
    host_it = stacked_batches(lm_batch_stream(tokens, B, T), K)
    for _ in range(5):
        s_host, m_host = host_step(s_host, next(host_it))

    data = stage_lm_data(tokens, B, T)
    dev_step = make_device_lm_train_step(loss_fn, opt, data, steps_per_call=K)
    s_dev = init_train_state(params, opt, jax.random.PRNGKey(1))
    idx = window_index_stream(data, K)
    for _ in range(5):
        s_dev, m_dev = dev_step(s_dev, data.arrays, next(idx))

    np.testing.assert_allclose(float(m_host["loss"]), float(m_dev["loss"]), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
        jax.device_get(s_host.params), jax.device_get(s_dev.params),
    )


def test_device_data_wraps_epochs():
    """Host stream wraps epochs by restarting; the window index stream must
    visit the same windows in the same order across the wrap."""
    tokens = _tokens(B * T * 3 + 1)  # 3 windows; K=4 wraps mid-call
    data = stage_lm_data(tokens, B, T)
    assert data.n_windows == 3
    idx = window_index_stream(data, K)
    starts = [int(next(idx)) for _ in range(4)]
    assert starts == [0, 1, 2, 0]  # (0+4)%3=1, (1+4)%3=2, ...


def test_device_data_dp_matches_single():
    tokens = _tokens()
    cfg = _cfg()

    def loss_fn(p, b, r):
        return lm_loss(p, b, cfg)

    opt = make_optimizer("sgd", 0.3)
    # a host copy: both runs start from it, and each step donates its state
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), cfg))

    data1 = stage_lm_data(tokens, B, T)
    step1 = make_device_lm_train_step(loss_fn, opt, data1, steps_per_call=K)
    s1 = init_train_state(params, opt, jax.random.PRNGKey(1))
    idx1 = window_index_stream(data1, K)
    for _ in range(3):
        s1, m1 = step1(s1, data1.arrays, next(idx1))

    mesh = make_mesh(dp=4, devices=np.asarray(jax.devices()[:4]))
    data4 = stage_lm_data(tokens, B, T, mesh=mesh)
    step4 = make_device_dp_lm_train_step(loss_fn, opt, data4, mesh, steps_per_call=K)
    s4 = init_train_state(replicate(params, mesh), opt, jax.random.PRNGKey(1))
    idx4 = window_index_stream(data4, K)
    for _ in range(3):
        s4, m4 = step4(s4, data4.arrays, next(idx4))

    # same global batch (streams sharded by row), grads pmean'd → same update
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        jax.device_get(s1.params), jax.device_get(s4.params),
    )


def test_device_data_stateful_matches_host():
    """Stateful TBPTT carries stay aligned (stream order is identical)."""
    from lstm_tensorspark_tpu.models.lstm_lm import init_carries

    tokens = _tokens()
    cfg = _cfg()

    def loss_fn(p, b, r, carries):
        return lm_loss(p, b, cfg, carries=carries)

    opt = make_optimizer("sgd", 0.3)
    # a host copy: both runs start from it, and each step donates its state
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), cfg))
    carries0 = jax.device_get(init_carries(cfg, B))

    host_step = make_multi_train_step(loss_fn, opt, stateful=True)
    s_host = init_train_state(params, opt, jax.random.PRNGKey(1), carries=carries0)
    host_it = stacked_batches(lm_batch_stream(tokens, B, T), K)
    for _ in range(4):
        s_host, _ = host_step(s_host, next(host_it))

    data = stage_lm_data(tokens, B, T)
    dev_step = make_device_lm_train_step(
        loss_fn, opt, data, steps_per_call=K, stateful=True
    )
    s_dev = init_train_state(params, opt, jax.random.PRNGKey(1), carries=carries0)
    idx = window_index_stream(data, K)
    for _ in range(4):
        s_dev, _ = dev_step(s_dev, data.arrays, next(idx))

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
        jax.device_get(s_host.params), jax.device_get(s_dev.params),
    )


def test_forecast_device_matches_host(tmp_path):
    """Forecaster --device-data must produce the identical loss stream to
    the host-fed path (same shuffled window order)."""
    import json

    from lstm_tensorspark_tpu.cli import main

    common = [
        "--dataset", "uci_electricity", "--hidden-units", "16",
        "--batch-size", "8", "--seq-len", "24", "--num-steps", "4",
        "--log-every", "1", "--backend", "single",
        "--compute-dtype", "float32", "--learning-rate", "0.1",
    ]
    host_log, dev_log = tmp_path / "host.jsonl", tmp_path / "dev.jsonl"
    assert main([*common, "--jsonl", str(host_log)]) == 0
    assert main([*common, "--device-data", "--steps-per-call", "2",
                 "--jsonl", str(dev_log)]) == 0

    def losses(p):
        return [r["loss"] for r in map(json.loads, p.read_text().splitlines())
                if "loss" in r]

    h, d = losses(host_log), losses(dev_log)
    # device path logs K-step means; compare the final eval instead
    def final_mse(p):
        recs = [json.loads(l) for l in p.read_text().splitlines()]
        return [r["eval_mse"] for r in recs if "eval_mse" in r][-1]

    np.testing.assert_allclose(final_mse(host_log), final_mse(dev_log),
                               rtol=1e-5)
    assert h and d


def test_classifier_device_matches_host(tmp_path):
    """Classifier --device-data: identical final accuracy/loss to host-fed
    (same shuffle+bucket order, gathers reproduce padded rows)."""
    import json

    from lstm_tensorspark_tpu.cli import main

    common = [
        "--dataset", "imdb", "--hidden-units", "16", "--batch-size", "16",
        "--seq-len", "40", "--num-steps", "6", "--log-every", "1",
        "--backend", "single", "--compute-dtype", "float32",
        "--optimizer", "adam", "--learning-rate", "1e-2",
    ]
    host_log, dev_log = tmp_path / "host.jsonl", tmp_path / "dev.jsonl"
    assert main([*common, "--jsonl", str(host_log)]) == 0
    assert main([*common, "--device-data", "--steps-per-call", "3",
                 "--jsonl", str(dev_log)]) == 0

    def final(p, key):
        recs = [json.loads(l) for l in p.read_text().splitlines()]
        return [r[key] for r in recs if key in r][-1]

    np.testing.assert_allclose(
        final(host_log, "eval_loss"), final(dev_log, "eval_loss"), rtol=1e-4
    )
    np.testing.assert_allclose(
        final(host_log, "eval_accuracy"), final(dev_log, "eval_accuracy"),
        rtol=1e-6,
    )


def test_forecast_device_dp_runs():
    """Forecaster device-data under DP (replicated series, sharded starts)."""
    from lstm_tensorspark_tpu.cli import main

    rc = main([
        "--dataset", "uci_electricity", "--hidden-units", "16",
        "--batch-size", "16", "--seq-len", "24", "--num-steps", "4",
        "--log-every", "2", "--num-partitions", "4", "--device-data",
        "--steps-per-call", "2", "--compute-dtype", "float32",
    ])
    assert rc == 0
