"""Non-finite step guard (train/loop.py): NaN/Inf losses or gradients skip
the update (params, moments, carries untouched), are counted in
``metrics["anomalous"]``, and — with ``anomaly_limit`` — abort with the
dedicated error after K consecutive bad steps. The NaN bursts come from the
fault plane, so this also covers ``nan_grads`` injection end to end."""

import os
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from lstm_tensorspark_tpu.resilience import faults
from lstm_tensorspark_tpu.train.loop import (
    AnomalousTrainingError,
    init_train_state,
    make_train_step,
    train_loop,
)


@pytest.fixture(autouse=True)
def _clean_plane(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.disarm()
    yield
    # explicit pop, not monkeypatch: the CLI EXPORTS the var mid-test
    # (--faults -> env for children) and delenv-on-absent records no undo
    os.environ.pop(faults.ENV_VAR, None)
    faults.disarm()


def _loss_fn(params, batch, rng):
    pred = params["w"] * batch["x"]
    return jnp.mean((pred - batch["y"]) ** 2), {}


def _batch(x, y):
    return {"x": jnp.asarray(x, jnp.float32), "y": jnp.asarray(y, jnp.float32)}


def _state(w=2.0):
    opt = optax.sgd(0.1)
    return (init_train_state({"w": jnp.asarray(w)}, opt,
                             jax.random.PRNGKey(0)), opt)


def test_nan_batch_skips_update_and_counts():
    state, opt = _state()
    step = make_train_step(_loss_fn, opt, jit=True)
    bad = _batch([jnp.nan, 1.0], [0.0, 0.0])
    good = _batch([1.0, 2.0], [0.0, 0.0])

    w0 = float(state.params["w"])  # read before the step donates the state
    s1, m1 = step(state, bad)
    assert float(m1["anomalous"]) == 1.0
    assert not np.isfinite(float(m1["loss"]))
    # update skipped: params and moments bit-identical, step/rng advanced
    w1 = float(s1.params["w"])
    assert w1 == w0
    assert int(s1.step) == 1

    s2, m2 = step(s1, good)
    assert float(m2["anomalous"]) == 0.0
    assert float(s2.params["w"]) != w1  # healthy again
    assert np.isfinite(float(s2.params["w"]))


def test_injected_nan_burst_matches_skip_replay():
    """nan_grads@2x2 poisons steps 2-3; the final params must equal a clean
    run that simply never took those two steps (proof the burst cannot
    leak into params or moments)."""
    good = [_batch([1.0, 2.0], [0.5, 0.1]), _batch([3.0, 1.0], [0.2, 0.9]),
            _batch([2.0, 2.0], [0.1, 0.3]), _batch([1.5, 0.5], [0.4, 0.2])]

    faults.arm("nan_grads@2x2")
    state, opt = _state()
    step = make_train_step(_loss_fn, opt, jit=True)
    flags = []
    for b in good:
        state, m = step(state, b)
        flags.append(float(m["anomalous"]))
    assert flags == [0.0, 1.0, 1.0, 0.0]
    faulted_w = float(state.params["w"])

    faults.disarm()
    ref, opt2 = _state()
    ref_step = make_train_step(_loss_fn, opt2, jit=True)
    ref, _ = ref_step(ref, good[0])
    # steps 2-3 skipped everything except step/rng advance
    ref = ref._replace(step=ref.step + 2,
                       rng=jax.random.split(jax.random.split(ref.rng)[0])[0])
    ref, _ = ref_step(ref, good[3])
    assert faulted_w == pytest.approx(float(ref.params["w"]), abs=1e-6)
    assert int(state.step) == 4


def test_multistep_counts_anomalous_in_window():
    from lstm_tensorspark_tpu.train.multistep import make_multi_train_step

    faults.arm("nan_grads@2x2")
    state, opt = _state()
    mstep = make_multi_train_step(_loss_fn, opt, jit=True)
    stacked = {"x": jnp.ones((4, 2), jnp.float32),
               "y": jnp.zeros((4, 2), jnp.float32)}
    state, ms = mstep(state, stacked)
    assert float(ms["anomalous"]) == 2.0
    assert np.isfinite(float(state.params["w"]))


def test_train_loop_aborts_after_k_consecutive():
    faults.arm("nan_grads@1x50")
    state, opt = _state()
    step = make_train_step(_loss_fn, opt, jit=True)
    batches = iter([_batch([1.0, 1.0], [0.0, 0.0])] * 50)
    with pytest.raises(AnomalousTrainingError) as ei:
        train_loop(state, step, batches, num_steps=50, log_every=0,
                   anomaly_limit=3)
    assert ei.value.consecutive == 3
    assert ei.value.total == 3


def test_train_loop_burst_below_limit_completes():
    faults.arm("nan_grads@2x2")
    state, opt = _state()
    step = make_train_step(_loss_fn, opt, jit=True)
    batches = iter([_batch([1.0, 1.0], [0.0, 0.0])] * 8)
    out = train_loop(state, step, batches, num_steps=8, log_every=0,
                     anomaly_limit=3)
    assert int(out.step) == 8
    assert np.isfinite(float(out.params["w"]))


def test_cli_anomaly_abort_exit_code(tmp_path, monkeypatch):
    """Full CLI path: a persistent NaN burst with --anomaly-limit returns
    the dedicated rc, and the checkpoints on disk stay clean."""
    from lstm_tensorspark_tpu.cli import main as cli_main
    from lstm_tensorspark_tpu.resilience.exit_codes import ANOMALY_RC

    rc = cli_main([
        "--dataset", "ptb_char", "--hidden-units", "8", "--batch-size", "8",
        "--seq-len", "16", "--backend", "single", "--num-steps", "10",
        "--log-every", "1", "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--checkpoint-every", "2", "--jsonl", str(tmp_path / "m.jsonl"),
        "--faults", "nan_grads@3x50", "--anomaly-limit", "4",
    ])
    assert rc == ANOMALY_RC
