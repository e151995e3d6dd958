"""The train state is donated into the step program (train/loop.py): every
step builder, called with its defaults on a state from the normal init path,
takes the buffers it is handed; no two leaves of a state the program builds
or restores share a buffer (two that did made the first dispatch fail with
"Attempt to donate the same buffer twice"); and a CLI run says in its
closing ``metrics_snapshot`` that the donation engaged."""

import json

import jax
import numpy as np
import pytest

from lstm_tensorspark_tpu.data import stage_lm_data
from lstm_tensorspark_tpu.models import (
    ClassifierConfig,
    LMConfig,
    Seq2SeqConfig,
    init_classifier,
    init_lm,
    init_seq2seq,
    lm_loss,
)
from lstm_tensorspark_tpu.models.lstm_lm import init_carries
from lstm_tensorspark_tpu.parallel import (
    make_dp_train_step,
    make_mesh,
    make_pp_lm_train_step,
    make_sharded_lm_train_step,
    make_tp_train_step,
    place_lm_params,
    place_pp_lm_params,
    shard_batch,
    stack_lm_params,
)
from lstm_tensorspark_tpu.parallel.data_parallel import replicate
from lstm_tensorspark_tpu.parallel.zero import (
    make_zero1_opt_init,
    make_zero1_train_step,
)
from lstm_tensorspark_tpu.train import (
    make_device_dp_lm_train_step,
    make_device_lm_train_step,
    make_dp_multi_train_step,
    make_multi_train_step,
    make_optimizer,
    make_train_step,
)
from lstm_tensorspark_tpu.train.checkpoint import Checkpointer
from lstm_tensorspark_tpu.train.loop import init_train_state

V, H, B, T, K = 11, 16, 8, 8, 2
CFG = LMConfig(vocab_size=V, hidden_size=H, num_layers=2)


def _loss_fn(params, batch, rng):
    return lm_loss(params, batch, CFG)


def _batch(k=None):
    rng = np.random.RandomState(0)
    shape = (B, T) if k is None else (k, B, T)
    return {"inputs": rng.randint(0, V, shape).astype(np.int32),
            "targets": rng.randint(0, V, shape).astype(np.int32)}


def _tokens():
    return np.random.RandomState(0).randint(0, V, B * T * 6 + 1).astype(np.int32)


def _fresh(opt, params=None):
    params = init_lm(jax.random.PRNGKey(0), CFG) if params is None else params
    return init_train_state(params, opt, jax.random.PRNGKey(1))


def _replicated(opt, mesh):
    s = _fresh(opt)
    return s._replace(step=replicate(s.step, mesh), rng=replicate(s.rng, mesh),
                      params=replicate(s.params, mesh),
                      opt_state=replicate(s.opt_state, mesh))


# each case: (dispatch, state) with dispatch(state) -> (state, metrics),
# the builder called with its defaults


def _single(opt):
    step = make_train_step(_loss_fn, opt)
    return (lambda s: step(s, _batch())), _fresh(opt)


def _multi(opt):
    step = make_multi_train_step(_loss_fn, opt)
    return (lambda s: step(s, _batch(K))), _fresh(opt)


def _dp(opt):
    mesh = make_mesh(dp=4, devices=np.asarray(jax.devices()[:4]))
    step = make_dp_train_step(_loss_fn, opt, mesh)
    batch = shard_batch(_batch(), mesh)
    return (lambda s: step(s, batch)), _replicated(opt, mesh)


def _dp_multi(opt):
    mesh = make_mesh(dp=4, devices=np.asarray(jax.devices()[:4]))
    step = make_dp_multi_train_step(_loss_fn, opt, mesh)
    batch = shard_batch(_batch(K), mesh, dim=1)
    return (lambda s: step(s, batch)), _replicated(opt, mesh)


def _device_data(opt):
    data = stage_lm_data(_tokens(), B, T)
    step = make_device_lm_train_step(_loss_fn, opt, data, steps_per_call=K)
    return (lambda s: step(s, data.arrays, np.int32(0))), _fresh(opt)


def _device_data_dp(opt):
    mesh = make_mesh(dp=4, devices=np.asarray(jax.devices()[:4]))
    data = stage_lm_data(_tokens(), B, T, mesh=mesh)
    step = make_device_dp_lm_train_step(_loss_fn, opt, data, mesh,
                                        steps_per_call=K)
    return ((lambda s: step(s, data.arrays, np.int32(0))),
            _replicated(opt, mesh))


def _tp(opt):
    mesh = make_mesh(dp=2, tp=2, devices=np.asarray(jax.devices()[:4]))
    params = init_lm(jax.random.PRNGKey(0), CFG)
    step = make_tp_train_step(_loss_fn, opt, mesh, params)
    return ((lambda s: step(s, _batch())),
            _fresh(opt, place_lm_params(params, mesh)))


def _three_d(opt):
    mesh = make_mesh(dp=2, tp=2, sp=2)
    params = init_lm(jax.random.PRNGKey(0), CFG)
    step = make_sharded_lm_train_step(CFG, opt, mesh, params, microbatches=2)
    return ((lambda s: step(s, _batch())),
            _fresh(opt, place_lm_params(params, mesh)))


def _pipeline_parts(opt):
    mesh = make_mesh(dp=2, pp=2, devices=np.asarray(jax.devices()[:4]))
    stacked = stack_lm_params(init_lm(jax.random.PRNGKey(0), CFG))
    return mesh, stacked, _fresh(opt, place_pp_lm_params(stacked, mesh))


def _pipeline(opt):
    mesh, stacked, state = _pipeline_parts(opt)
    step = make_pp_lm_train_step(CFG, opt, mesh, stacked, microbatches=2)
    return (lambda s: step(s, _batch())), state


def _zero1(opt):
    mesh = make_mesh(dp=4, devices=np.asarray(jax.devices()[:4]))
    step = make_zero1_train_step(_loss_fn, opt, mesh)
    s = _replicated(opt, mesh)
    s = s._replace(opt_state=make_zero1_opt_init(opt, mesh)(s.params))
    batch = shard_batch(_batch(), mesh)
    return (lambda st: step(st, batch)), s


BUILDERS = {
    "single": _single, "multistep": _multi, "dp": _dp,
    "dp_multistep": _dp_multi, "device_data": _device_data,
    "device_data_dp": _device_data_dp, "tp": _tp, "dp_tp_sp": _three_d,
    "pipeline": _pipeline, "zero1": _zero1,
}


@pytest.mark.parametrize("name", sorted(BUILDERS))
def test_builder_donates_the_state_it_is_handed(name):
    dispatch, state = BUILDERS[name](make_optimizer("adam", 1e-2))
    for _ in range(2):
        handed = [x for x in jax.tree.leaves(state) if isinstance(x, jax.Array)]
        assert handed
        state, metrics = dispatch(state)
        assert np.isfinite(float(metrics["loss"]))
        alive = [x.shape for x in handed if not x.is_deleted()]
        assert not alive, f"{name}: {len(alive)} of {len(handed)} leaves kept"
    assert int(state.step) in (2, 2 * K)


def _buffers(leaf):
    """The addresses of the memory a leaf occupies. The shards of one
    replicated leaf may repeat an address here: on the CPU they can all
    stand on the one host array they were placed from."""
    if isinstance(leaf, jax.Array):
        return {s.data.unsafe_buffer_pointer() for s in leaf.addressable_shards}
    return {np.asarray(leaf).__array_interface__["data"][0]}


def _lm_state(opt, *, tie=False, stateful=False):
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2,
                   tie_embeddings=tie)
    return init_train_state(
        init_lm(jax.random.PRNGKey(0), cfg), opt, jax.random.PRNGKey(1),
        carries=init_carries(cfg, B) if stateful else None)


def _classifier_state(opt):
    cfg = ClassifierConfig(vocab_size=V, hidden_size=H, num_layers=2)
    return init_train_state(init_classifier(jax.random.PRNGKey(0), cfg), opt,
                            jax.random.PRNGKey(1))


def _seq2seq_state(opt):
    cfg = Seq2SeqConfig(num_features=3, hidden_size=H, num_layers=2, horizon=4)
    return init_train_state(init_seq2seq(jax.random.PRNGKey(0), cfg), opt,
                            jax.random.PRNGKey(1))


def _restored(state, tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(state)
    ck.wait()
    return ck.restore_latest(state)  # cli.py's template is the placed state


STATES = {
    "lm_untied": lambda opt, tmp: _lm_state(opt),
    "lm_tied": lambda opt, tmp: _lm_state(opt, tie=True),
    "lm_stateful": lambda opt, tmp: _lm_state(opt, stateful=True),
    "classifier_bilstm": lambda opt, tmp: _classifier_state(opt),
    "seq2seq": lambda opt, tmp: _seq2seq_state(opt),
    "lm_replicated": lambda opt, tmp: _replicated(opt, make_mesh(dp=8)),
    "lm_pipeline_stacked": lambda opt, tmp: _pipeline_parts(opt)[2],
    "restored": lambda opt, tmp: _restored(_lm_state(opt, stateful=True), tmp),
    "restored_replicated": lambda opt, tmp: _restored(
        _replicated(opt, make_mesh(dp=8)), tmp),
}


@pytest.mark.parametrize("name", sorted(STATES))
def test_no_two_leaves_of_a_train_state_share_a_buffer(name, tmp_path):
    state = STATES[name](make_optimizer("adam", 1e-2), tmp_path)
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    assert len(leaves) > 20
    owner = {}
    for path, leaf in leaves:
        for address in _buffers(leaf):
            assert address not in owner, (
                f"{jax.tree_util.keystr(path)} shares a buffer with "
                f"{jax.tree_util.keystr(owner[address])}")
            owner[address] = path


@pytest.mark.parametrize("flags", [
    [],  # host-fed, one step a dispatch
    ["--steps-per-call", "2", "--device-data"],  # the train cells' form
], ids=["host_fed", "device_data_k2"])
def test_cli_run_logs_train_state_donated(flags, tmp_path):
    from lstm_tensorspark_tpu.cli import main

    # one partition: the 8-way DP CLI runs abort in `evaluate` under a loaded
    # test machine (PERF.md section 7); DP builders are held above
    jsonl = tmp_path / "metrics.jsonl"
    rc = main(["--dataset", "ptb_char", "--hidden-units", "16",
               "--batch-size", "8", "--seq-len", "8", "--num-steps", "4",
               "--log-every", "2", "--compute-dtype", "float32",
               "--num-partitions", "1", "--jsonl", str(jsonl), *flags])
    assert rc == 0
    records = [json.loads(line) for line in jsonl.read_text().splitlines()]
    (snapshot,) = [r for r in records if r.get("note") == "metrics_snapshot"]
    assert snapshot["train_state_donated"] == 1
