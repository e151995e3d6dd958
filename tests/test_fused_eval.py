"""Fused train+eval executable (train/device_step.py): the lax.cond-gated
on-device eval must equal the host-driven `evaluate()` exactly, and
non-eval calls must be bit-identical to the plain device-data step."""

import json

import jax
import numpy as np

from lstm_tensorspark_tpu.data import (
    lm_epoch_batches,
    stage_lm_data,
    window_index_stream,
)
from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.models.lstm_lm import init_carries
from lstm_tensorspark_tpu.parallel import make_mesh, shard_batch
from lstm_tensorspark_tpu.parallel.data_parallel import replicate
from lstm_tensorspark_tpu.train import (
    make_device_dp_lm_train_step,
    make_device_lm_train_step,
    make_eval_step,
    make_optimizer,
)
from lstm_tensorspark_tpu.train.loop import evaluate, init_train_state

B, T, V, H, K = 8, 16, 29, 16, 4


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, V, n).astype(np.int32)


def _setup(stateful=False):
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2)

    if stateful:

        def loss_fn(p, b, r, carries):
            return lm_loss(p, b, cfg, carries=carries)

    else:

        def loss_fn(p, b, r):
            return lm_loss(p, b, cfg)

    opt = make_optimizer("sgd", 0.3)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    train_tokens = _tokens(B * T * 8 + 1)
    valid_tokens = _tokens(B * T * 3 + 1, seed=1)
    carries0 = init_carries(cfg, B) if stateful else None
    # a host copy: tests hand it to two steps, and each donates what it gets
    state = jax.device_get(
        init_train_state(params, opt, jax.random.PRNGKey(1), carries=carries0))
    return cfg, loss_fn, opt, state, train_tokens, valid_tokens


def test_fused_eval_matches_host_evaluate():
    cfg, loss_fn, opt, state, train_tokens, valid_tokens = _setup()
    ddata = stage_lm_data(train_tokens, B, T)
    edata = stage_lm_data(valid_tokens, B, T)
    step = make_device_lm_train_step(
        loss_fn, opt, ddata, eval_data=edata, steps_per_call=K
    )
    state, ms = step(state, ddata.arrays, np.int32(0), edata.arrays,
                     np.bool_(True))
    # host-driven eval on the SAME post-update params
    host = evaluate(
        make_eval_step(loss_fn), state.params,
        lm_epoch_batches(valid_tokens, B, T),
    )
    np.testing.assert_allclose(
        float(ms["eval_loss"]), host["eval_loss"], rtol=1e-6
    )


def test_fused_no_eval_is_bit_identical_to_plain_step():
    cfg, loss_fn, opt, state, train_tokens, valid_tokens = _setup()
    ddata = stage_lm_data(train_tokens, B, T)
    edata = stage_lm_data(valid_tokens, B, T)
    fused = make_device_lm_train_step(
        loss_fn, opt, ddata, eval_data=edata, steps_per_call=K
    )
    plain = make_device_lm_train_step(loss_fn, opt, ddata, steps_per_call=K)

    sf, mf = fused(state, ddata.arrays, np.int32(0), edata.arrays,
                   np.bool_(False))
    sp, mp = plain(state, ddata.arrays, np.int32(0))
    assert np.isnan(float(mf["eval_loss"]))
    np.testing.assert_array_equal(np.asarray(mf["loss"]), np.asarray(mp["loss"]))
    for a, b in zip(jax.tree.leaves(sf.params), jax.tree.leaves(sp.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_eval_windows_cap():
    cfg, loss_fn, opt, state, train_tokens, valid_tokens = _setup()
    ddata = stage_lm_data(train_tokens, B, T)
    edata = stage_lm_data(valid_tokens, B, T)
    assert edata.n_windows >= 2
    step = make_device_lm_train_step(
        loss_fn, opt, ddata, eval_data=edata, steps_per_call=K, eval_windows=1
    )
    state, ms = step(state, ddata.arrays, np.int32(0), edata.arrays,
                     np.bool_(True))
    from lstm_tensorspark_tpu.data.batching import cap_batches

    host = evaluate(
        make_eval_step(loss_fn), state.params,
        cap_batches(lm_epoch_batches(valid_tokens, B, T), 1),
    )
    np.testing.assert_allclose(
        float(ms["eval_loss"]), host["eval_loss"], rtol=1e-6
    )


def test_fused_eval_stateful_matches_host():
    cfg, loss_fn, opt, state, train_tokens, valid_tokens = _setup(stateful=True)
    ddata = stage_lm_data(train_tokens, B, T)
    edata = stage_lm_data(valid_tokens, B, T)
    step = make_device_lm_train_step(
        loss_fn, opt, ddata, eval_data=edata, steps_per_call=K, stateful=True
    )
    ev_carries0 = init_carries(cfg, B)
    state, ms = step(state, ddata.arrays, np.int32(0), edata.arrays,
                     np.bool_(True), ev_carries0)
    host = evaluate(
        make_eval_step(loss_fn, stateful=True), state.params,
        lm_epoch_batches(valid_tokens, B, T),
        carries=init_carries(cfg, B),
    )
    np.testing.assert_allclose(
        float(ms["eval_loss"]), host["eval_loss"], rtol=1e-6
    )


def test_fused_eval_dp_matches_single():
    cfg, loss_fn, opt, state, train_tokens, valid_tokens = _setup()
    mesh = make_mesh(dp=8)
    ddata_s = stage_lm_data(train_tokens, B, T)
    edata_s = stage_lm_data(valid_tokens, B, T)
    single = make_device_lm_train_step(
        loss_fn, opt, ddata_s, eval_data=edata_s, steps_per_call=K
    )
    s1, m1 = single(state, ddata_s.arrays, np.int32(0), edata_s.arrays,
                    np.bool_(True))

    ddata = stage_lm_data(train_tokens, B, T, mesh=mesh)
    edata = stage_lm_data(valid_tokens, B, T, mesh=mesh)
    dp = make_device_dp_lm_train_step(
        loss_fn, opt, ddata, mesh, eval_data=edata, steps_per_call=K
    )
    state_dp = state._replace(
        params=replicate(state.params, mesh),
        opt_state=replicate(state.opt_state, mesh),
    )
    s2, m2 = dp(state_dp, ddata.arrays, np.int32(0), edata.arrays,
                np.bool_(True), None)
    # same global batch, same windows → same training and same eval value
    np.testing.assert_allclose(
        float(m1["eval_loss"]), float(m2["eval_loss"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(m1["loss"]), float(m2["loss"]), rtol=1e-5
    )


def test_cli_fused_eval_end_to_end(tmp_path):
    from lstm_tensorspark_tpu.cli import main

    jsonl = tmp_path / "m.jsonl"
    rc = main([
        "--dataset", "ptb_char", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "8", "--seq-len", "16", "--num-steps", "8",
        "--steps-per-call", "2", "--device-data", "--fused-eval",
        "--eval-every", "2", "--log-every", "1", "--backend", "single",
        "--jsonl", str(jsonl),
    ])
    assert rc == 0
    records = [json.loads(l) for l in open(jsonl)]
    evals = [r for r in records if "eval_ppl" in r and r.get("note") != "final"]
    assert len(evals) >= 2, records
    assert all(np.isfinite(r["eval_ppl"]) for r in evals)
    # the final record comes from the HOST eval path on the same params —
    # the two implementations cross-check each other at the last eval step
    final = [r for r in records if r.get("note") == "final"][0]
    last = [r for r in evals if r["step"] == final["step"]]
    assert last, (evals, final)  # a fused eval MUST land on the final step
    np.testing.assert_allclose(
        last[0]["eval_loss"], final["eval_loss"], rtol=1e-5
    )


def test_cli_fused_eval_classifier_matches_host_final(tmp_path):
    """The classifier's fused eval and its host eval_fn share the last step's
    params (the 'final' record) — they must agree to float tolerance."""
    from lstm_tensorspark_tpu.cli import main

    jsonl = tmp_path / "c.jsonl"
    rc = main([
        "--dataset", "imdb", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "8", "--seq-len", "32", "--num-steps", "6",
        "--steps-per-call", "2", "--device-data", "--fused-eval",
        "--eval-every", "3", "--log-every", "1", "--backend", "single",
        "--learning-rate", "0.1", "--jsonl", str(jsonl),
    ])
    assert rc == 0
    records = [json.loads(l) for l in open(jsonl)]
    evals = [r for r in records
             if "eval_accuracy" in r and r.get("note") != "final"]
    assert evals, records
    final = [r for r in records if r.get("note") == "final"][0]
    last = [r for r in evals if r["step"] == final["step"]]
    assert last, (evals, final)
    np.testing.assert_allclose(
        last[0]["eval_loss"], final["eval_loss"], rtol=1e-5
    )
    np.testing.assert_allclose(
        last[0]["eval_accuracy"], final["eval_accuracy"], rtol=1e-5
    )


def test_cli_fused_eval_forecaster_matches_host_final(tmp_path):
    from lstm_tensorspark_tpu.cli import main

    jsonl = tmp_path / "f.jsonl"
    rc = main([
        "--dataset", "uci_electricity", "--hidden-units", "16",
        "--num-layers", "1", "--batch-size", "8", "--seq-len", "24",
        "--num-steps", "6", "--steps-per-call", "2", "--device-data",
        "--fused-eval", "--eval-every", "3", "--log-every", "1",
        "--backend", "single", "--learning-rate", "0.05",
        "--jsonl", str(jsonl),
    ])
    assert rc == 0
    records = [json.loads(l) for l in open(jsonl)]
    evals = [r for r in records if "eval_mse" in r and r.get("note") != "final"]
    assert evals, records
    final = [r for r in records if r.get("note") == "final"][0]
    last = [r for r in evals if r["step"] == final["step"]]
    assert last, (evals, final)
    np.testing.assert_allclose(last[0]["eval_mse"], final["eval_mse"],
                               rtol=1e-4)
    np.testing.assert_allclose(last[0]["eval_mae"], final["eval_mae"],
                               rtol=1e-4)


def test_cli_fused_eval_dp_classifier(tmp_path):
    """Fused eval under the DP backend (replicated eval batches) runs and
    logs finite metrics on the 8-device mesh."""
    from lstm_tensorspark_tpu.cli import main

    jsonl = tmp_path / "d.jsonl"
    rc = main([
        "--dataset", "imdb", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "16", "--seq-len", "32", "--num-steps", "4",
        "--steps-per-call", "2", "--device-data", "--fused-eval",
        "--eval-every", "2", "--log-every", "1", "--backend", "dp",
        "--num-partitions", "8", "--learning-rate", "0.1",
        "--jsonl", str(jsonl),
    ])
    assert rc == 0
    records = [json.loads(l) for l in open(jsonl)]
    evals = [r for r in records
             if "eval_accuracy" in r and r.get("note") != "final"]
    assert evals and all(np.isfinite(r["eval_accuracy"]) for r in evals)


def test_cli_fused_eval_host_fed_lm_matches_device_data(tmp_path):
    """--fused-eval without --device-data (host-fed train feed, staged eval
    stream): must produce the SAME eval records as the device-data run —
    identical data order (tests/test_device_data.py) + identical eval."""
    from lstm_tensorspark_tpu.cli import main

    argv = [
        "--dataset", "ptb_char", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "8", "--seq-len", "16", "--num-steps", "8",
        "--steps-per-call", "2", "--fused-eval", "--eval-every", "2",
        "--log-every", "1", "--backend", "single",
    ]
    a, b = tmp_path / "host.jsonl", tmp_path / "dev.jsonl"
    assert main(argv + ["--jsonl", str(a)]) == 0
    assert main(argv + ["--device-data", "--jsonl", str(b)]) == 0

    def evals(p):
        return [(r["step"], r["eval_loss"]) for r in map(json.loads, open(p))
                if "eval_loss" in r]

    ea, eb = evals(a), evals(b)
    assert ea and [s for s, _ in ea] == [s for s, _ in eb]
    np.testing.assert_allclose([v for _, v in ea], [v for _, v in eb],
                               rtol=1e-6)


def test_cli_fused_eval_host_fed_k1_single_step(tmp_path):
    """Host-fed fused eval at --steps-per-call 1 (the K=1 stacked path)."""
    from lstm_tensorspark_tpu.cli import main

    jsonl = tmp_path / "k1.jsonl"
    rc = main([
        "--dataset", "ptb_char", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "8", "--seq-len", "16", "--num-steps", "4",
        "--fused-eval", "--eval-every", "2", "--log-every", "1",
        "--backend", "single", "--jsonl", str(jsonl),
    ])
    assert rc == 0
    records = [json.loads(l) for l in open(jsonl)]
    evals = [r for r in records if "eval_ppl" in r and r.get("note") != "final"]
    final = [r for r in records if r.get("note") == "final"][0]
    last = [r for r in evals if r["step"] == final["step"]]
    assert last, (evals, final)
    np.testing.assert_allclose(last[0]["eval_loss"], final["eval_loss"],
                               rtol=1e-5)


def test_cli_fused_eval_host_fed_forecaster_dp(tmp_path):
    """Host-fed fused eval for a task runner under the DP backend."""
    from lstm_tensorspark_tpu.cli import main

    jsonl = tmp_path / "fdp.jsonl"
    rc = main([
        "--dataset", "uci_electricity", "--hidden-units", "16",
        "--num-layers", "1", "--batch-size", "16", "--seq-len", "24",
        "--num-steps", "4", "--steps-per-call", "2", "--fused-eval",
        "--eval-every", "2", "--log-every", "1", "--backend", "dp",
        "--num-partitions", "8", "--learning-rate", "0.05",
        "--jsonl", str(jsonl),
    ])
    assert rc == 0
    records = [json.loads(l) for l in open(jsonl)]
    evals = [r for r in records if "eval_mse" in r and r.get("note") != "final"]
    final = [r for r in records if r.get("note") == "final"][0]
    last = [r for r in evals if r["step"] == final["step"]]
    assert last, (evals, final)
    np.testing.assert_allclose(last[0]["eval_mse"], final["eval_mse"],
                               rtol=1e-4)


def test_cli_fused_eval_rejected_with_tp():
    import pytest

    from lstm_tensorspark_tpu.cli import main

    with pytest.raises(SystemExit):
        main([
            "--dataset", "ptb_char", "--num-steps", "2", "--fused-eval",
            "--tensor-parallel", "2",
        ])


def test_cli_fused_eval_requires_eval_cadence():
    import pytest

    from lstm_tensorspark_tpu.cli import main

    with pytest.raises(SystemExit):
        main(["--dataset", "ptb_char", "--num-steps", "2", "--fused-eval"])


def test_cli_fused_eval_tp_classifier(tmp_path):
    """Fused eval under --tensor-parallel (GSPMD jit step + gated eval tail):
    fused and host evals must agree on the shared final step."""
    from lstm_tensorspark_tpu.cli import main

    jsonl = tmp_path / "tpc.jsonl"
    rc = main([
        "--dataset", "imdb", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "16", "--seq-len", "32", "--num-steps", "4",
        "--fused-eval", "--eval-every", "2", "--log-every", "1",
        "--tensor-parallel", "2", "--num-partitions", "2",
        "--learning-rate", "0.1", "--jsonl", str(jsonl),
    ])
    assert rc == 0
    records = [json.loads(l) for l in open(jsonl)]
    evals = [r for r in records
             if "eval_accuracy" in r and r.get("note") != "final"]
    final = [r for r in records if r.get("note") == "final"][0]
    last = [r for r in evals if r["step"] == final["step"]]
    assert last, (evals, final)
    np.testing.assert_allclose(last[0]["eval_loss"], final["eval_loss"],
                               rtol=1e-5)
    np.testing.assert_allclose(last[0]["eval_accuracy"],
                               final["eval_accuracy"], rtol=1e-5)


def test_cli_fused_eval_tp_forecaster(tmp_path):
    from lstm_tensorspark_tpu.cli import main

    jsonl = tmp_path / "tpf.jsonl"
    rc = main([
        "--dataset", "uci_electricity", "--hidden-units", "16",
        "--num-layers", "1", "--batch-size", "16", "--seq-len", "24",
        "--num-steps", "4", "--fused-eval", "--eval-every", "2",
        "--log-every", "1", "--tensor-parallel", "2",
        "--num-partitions", "2", "--learning-rate", "0.05",
        "--jsonl", str(jsonl),
    ])
    assert rc == 0
    records = [json.loads(l) for l in open(jsonl)]
    evals = [r for r in records if "eval_mse" in r and r.get("note") != "final"]
    final = [r for r in records if r.get("note") == "final"][0]
    last = [r for r in evals if r["step"] == final["step"]]
    assert last, (evals, final)
    np.testing.assert_allclose(last[0]["eval_mse"], final["eval_mse"],
                               rtol=1e-4)


def test_cli_fused_eval_rejected_with_lm_tp():
    import pytest

    from lstm_tensorspark_tpu.cli import main

    with pytest.raises(SystemExit):
        main([
            "--dataset", "ptb_char", "--num-steps", "2", "--fused-eval",
            "--eval-every", "2", "--tensor-parallel", "2",
        ])
