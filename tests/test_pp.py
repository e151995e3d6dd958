"""Pipeline parallelism (DP x PP wavefront over stacked layers): exact loss
and parameter parity with the single-device step over several steps."""

import jax
import numpy as np

from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.parallel import make_mesh
from lstm_tensorspark_tpu.parallel.pipeline_parallel import (
    make_pp_lm_train_step,
    place_pp_lm_params,
    stack_lm_params,
    unstack_lm_params,
)
from lstm_tensorspark_tpu.train import make_optimizer, make_train_step
from lstm_tensorspark_tpu.train.loop import init_train_state

V, H, B, T = 11, 16, 8, 12


def _batches(n, seed=0):
    rngb = np.random.RandomState(seed)
    return [
        {
            "inputs": rngb.randint(0, V, (B, T)).astype(np.int32),
            "targets": rngb.randint(0, V, (B, T)).astype(np.int32),
        }
        for _ in range(n)
    ]


def _single_device_run(cfg, params, batches, opt):
    def loss_fn(p, b, r):
        return lm_loss(p, b, cfg)

    step = make_train_step(loss_fn, opt)
    # a host copy: the step donates its state, and the caller's ``params``
    # also start the pipeline run this one is compared with
    s = init_train_state(jax.device_get(params), opt, jax.random.PRNGKey(1))
    losses = []
    for b in batches:
        s, m = step(s, b)
        losses.append(float(m["loss"]))
    return s, losses


def _pp_run(cfg, params, batches, opt, *, dp, pp, microbatches, tp=1,
            zero1=False):
    mesh = make_mesh(dp=dp, tp=tp, pp=pp)
    stacked = stack_lm_params(params)
    placed = place_pp_lm_params(stacked, mesh, tp=tp > 1)
    step = make_pp_lm_train_step(
        cfg, opt, mesh, stacked, microbatches=microbatches, donate=False,
        tp=tp > 1, zero1=zero1,
    )
    s = init_train_state(placed, opt, jax.random.PRNGKey(1))
    if zero1:
        from lstm_tensorspark_tpu.parallel.pipeline_parallel import (
            place_pp_zero1_opt_state,
        )

        s = s._replace(opt_state=place_pp_zero1_opt_state(
            s.opt_state, opt, stacked, mesh, tp=tp > 1))
    losses = []
    for b in batches:
        s, m = step(s, b)
        losses.append(float(m["loss"]))
    return s, losses


def test_dp_pp_matches_single_device():
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=4)
    opt = make_optimizer("sgd", 0.3)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    batches = _batches(3)

    s0, want = _single_device_run(cfg, params, batches, opt)
    s1, got = _pp_run(cfg, params, batches, opt, dp=2, pp=4, microbatches=4)

    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-5
        ),
        jax.device_get(unstack_lm_params(s1.params)),
        jax.device_get(s0.params),
    )


def test_pp_adam_multilayer_stage():
    """2 stages x 2 layers each, adam (exercises sharded opt-state moments),
    single microbatch (pure memory-scaling mode)."""
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=4)
    opt = make_optimizer("adam", 1e-2)
    params = init_lm(jax.random.PRNGKey(2), cfg)
    batches = _batches(2, seed=3)

    _, want = _single_device_run(cfg, params, batches, opt)
    _, got = _pp_run(cfg, params, batches, opt, dp=4, pp=2, microbatches=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_pp_embed_neq_hidden_matches_single_device():
    """embed_size != hidden_size: the zero-padded layer stack must give
    EXACT parity (padded W rows multiply zero lanes; dW_pad = 0)."""
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2, embed_size=8)
    opt = make_optimizer("sgd", 0.3)
    params = init_lm(jax.random.PRNGKey(4), cfg)
    batches = _batches(3, seed=5)

    s0, want = _single_device_run(cfg, params, batches, opt)
    s1, got = _pp_run(cfg, params, batches, opt, dp=4, pp=2, microbatches=2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # round-trip recovers the true (unpadded) per-layer shapes and values
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-5
        ),
        jax.device_get(unstack_lm_params(s1.params)),
        jax.device_get(s0.params),
    )


def test_pp_tp_composition_matches_single_device():
    """DP x TP x PP (hybrid manual-pipe/auto-model): loss parity over steps,
    with embed != hidden exercising the padded stack under TP too."""
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2, embed_size=8)
    opt = make_optimizer("sgd", 0.3)
    params = init_lm(jax.random.PRNGKey(6), cfg)
    batches = _batches(3, seed=7)

    _, want = _single_device_run(cfg, params, batches, opt)
    _, got = _pp_run(cfg, params, batches, opt, dp=2, pp=2, tp=2,
                     microbatches=2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_pp_dropout_trains():
    """Inter-layer dropout under PP: runs, loss finite, and the trajectory
    differs from the deterministic run (masks are real). (No learning
    assertion: targets are random and 50% dropout on H=16 makes short-run
    loss decrease unreliable.)"""
    opt = make_optimizer("sgd", 0.3)
    batches = _batches(6, seed=8)
    losses = {}
    for rate in (0.0, 0.5):
        cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2, dropout=rate)
        params = init_lm(jax.random.PRNGKey(9), cfg)
        _, ls = _pp_run(cfg, params, batches, opt, dp=4, pp=2, microbatches=2)
        assert np.isfinite(ls).all()
        losses[rate] = ls
    assert not np.allclose(losses[0.0], losses[0.5])  # masks took effect


def test_pp_sharded_eval_matches_single_device():
    """Sharded PP eval (no host gather) returns the same loss as the
    single-device lm_loss on identical params, and reports global tokens."""
    from lstm_tensorspark_tpu.models import lm_loss
    from lstm_tensorspark_tpu.parallel.pipeline_parallel import (
        make_pp_lm_eval_step,
    )

    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2, embed_size=8)
    params = init_lm(jax.random.PRNGKey(10), cfg)
    mesh = make_mesh(dp=2, tp=2, pp=2)
    stacked = stack_lm_params(params)
    placed = place_pp_lm_params(stacked, mesh, tp=True)
    ev = make_pp_lm_eval_step(cfg, mesh, stacked, microbatches=2, tp=True)
    b = _batches(1, seed=11)[0]
    m = ev(placed, b)
    want, _ = lm_loss(params, b, cfg)
    np.testing.assert_allclose(float(m["loss"]), float(want), rtol=1e-5)
    assert float(m["tokens"]) == B * T


def test_pp_with_pallas_interpret_matches_plain_pp(monkeypatch):
    """--use-pallas composes with --pipeline-stages (VERDICT r2 item 3): the
    stage-interior recurrences run the fused kernel (interpret mode on CPU,
    forced past the platform gate) and must match the plain-scan PP run and
    the single-device run."""
    import functools

    import lstm_tensorspark_tpu.ops.pallas_lstm as pallas_mod

    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=4)
    opt = make_optimizer("sgd", 0.3)
    params = init_lm(jax.random.PRNGKey(4), cfg)
    batches = _batches(3, seed=5)

    _, want = _single_device_run(cfg, params, batches, opt)
    _, plain = _pp_run(cfg, params, batches, opt, dp=2, pp=4, microbatches=4)

    monkeypatch.setattr(pallas_mod, "supported", lambda *a, **k: True)
    monkeypatch.setattr(
        pallas_mod, "pallas_lstm_scan",
        functools.partial(pallas_mod.pallas_lstm_scan, interpret=True),
    )
    cfg_p = LMConfig(vocab_size=V, hidden_size=H, num_layers=4,
                     use_pallas=True)
    _, got = _pp_run(cfg_p, params, batches, opt, dp=2, pp=4, microbatches=4)

    np.testing.assert_allclose(got, plain, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_pp_tp_keeps_pallas_off(monkeypatch):
    """With an auto "model" TP axis the stage interior must NOT take the
    fused path (GSPMD cannot partition pallas_call) even when use_pallas is
    set — the kernel entry would raise if reached (platform-gated off here),
    so plain parity passing proves the gate."""
    import lstm_tensorspark_tpu.ops.pallas_lstm as pallas_mod

    def boom(*a, **k):
        raise AssertionError("pallas dispatch must not be reached under TP")

    cfg_ref = LMConfig(vocab_size=V, hidden_size=H, num_layers=2)
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2, use_pallas=True)
    opt = make_optimizer("sgd", 0.3)
    params = init_lm(jax.random.PRNGKey(6), cfg)
    batches = _batches(2, seed=7)

    _, want = _single_device_run(cfg_ref, params, batches, opt)
    monkeypatch.setattr(pallas_mod, "supported", boom)
    _, got = _pp_run(cfg, params, batches, opt, dp=2, pp=2, microbatches=2,
                     tp=2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_zero1_pp_matches_plain_pp_trajectory():
    """ZeRO-1 x PP (VERDICT r3 item 6): stage x data sharded adam moments
    must not change the trajectory — the spec tree only moves WHERE the
    update computes, not what it computes."""
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=4)
    opt = make_optimizer("adam", 3e-3)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    batches = _batches(3)

    _, want = _pp_run(cfg, params, batches, opt, dp=2, pp=4, microbatches=4)
    s1, got = _pp_run(cfg, params, batches, opt, dp=2, pp=4, microbatches=4,
                      zero1=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    # the single-device oracle agrees too
    _, ref = _single_device_run(cfg, params, batches, opt)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_zero1_pp_moments_shard_over_pipe_and_data():
    """The memory claim: stacked-layer moment leaves end up sharded over
    BOTH pipe and data (1/(pp*dp) per chip), preserved across steps by the
    out_shardings pin; scalar leaves stay replicated."""
    from jax.sharding import PartitionSpec as P
    from jax.tree_util import GetAttrKey, tree_flatten_with_path

    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=4)
    opt = make_optimizer("adam", 3e-3)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    s1, _ = _pp_run(cfg, params, _batches(2), opt, dp=2, pp=4,
                    microbatches=4, zero1=True)
    leaves = tree_flatten_with_path(s1.opt_state)[0]
    layer_mats = [a for path, a in leaves
                  if GetAttrKey("mu") in path and a.ndim == 3]
    assert layer_mats, "expected stacked [L, ., .] moment leaves under .mu"
    for a in layer_mats:
        spec = a.sharding.spec
        assert "pipe" in spec and "data" in spec, spec
        shard = a.addressable_shards[0].data
        assert shard.size * 8 == a.size, (shard.shape, a.shape)
    counts = [a for path, a in leaves if GetAttrKey("count") in path]
    assert counts and all(c.sharding.spec == P() for c in counts)


def test_zero1_pp_tp_triple_composition():
    """zero1 x tp x pp on one mesh: trajectory parity with the
    single-device oracle at dp=2, tp=2, pp=2."""
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2)
    opt = make_optimizer("adam", 3e-3)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    batches = _batches(3)

    _, ref = _single_device_run(cfg, params, batches, opt)
    _, got = _pp_run(cfg, params, batches, opt, dp=2, pp=2, tp=2,
                     microbatches=2, zero1=True)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
