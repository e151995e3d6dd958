"""The data-parallel step's sharded weight update
(lstm_tensorspark_tpu/train/sharded_update.py) on four virtual devices: a
large leaf and its moments live sharded, a quarter a chip; a step
all-gathers the parameter, reduce-scatters its gradient and updates the
quarter.

Held to the REPLICATED step, kept here as the plain form: `pmean` of every
gradient, optax's own `clip_by_global_norm`, the whole update on every
chip — the program these builders made before the update was sharded.
"""

import collections
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from lstm_tensorspark_tpu.data import stage_lm_data
from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.models.lstm_lm import init_carries
from lstm_tensorspark_tpu.parallel import (
    data_parallel,
    make_dp_train_step,
    make_mesh,
    shard_batch,
)
from lstm_tensorspark_tpu.train import (
    device_step,
    make_device_dp_lm_train_step,
    make_dp_multi_train_step,
    make_optimizer,
    make_train_step,
    multistep,
    sharded_update,
)
from lstm_tensorspark_tpu.train.checkpoint import Checkpointer
from lstm_tensorspark_tpu.train.loop import init_train_state
from lstm_tensorspark_tpu.train.sharded_update import (
    dp_state_spec,
    place_dp_state,
    shard_dim,
    sharded_share,
)

DP, STEPS, CLIP = 4, 6, 0.05
B, T, K = 16, 8, 4
V, H = 96, 32
CFG = LMConfig(vocab_size=V, hidden_size=H, num_layers=1)
MB = 1024 * 1024


@pytest.fixture
def small_leaves_qualify(monkeypatch):
    """Every leaf of 8 KiB and more is a large one."""
    monkeypatch.setattr(sharded_update, "MIN_SHARDED_BYTES", 8 * 1024)


def _mesh(n=DP):
    return make_mesh(dp=n, devices=np.asarray(jax.devices()[:n]))


# ---- a toy model whose leaves meet every branch of the rule -----------------


def _toy_params():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    return jax.device_get({
        "rows": jax.random.normal(k[0], (64, 256)) * 0.1,    # dimension 0
        "lanes": jax.random.normal(k[1], (100, 512)) * 0.1,  # dimension 1
        "odd": jax.random.normal(k[2], (100, 130)) * 0.1,    # neither divides
        "bias": jnp.zeros((256,)),                           # small
    })


def _toy_loss(params, batch, rng):
    h = jnp.tanh(batch["x"] @ params["rows"] + params["bias"])
    y = h @ params["lanes"][:, :256].T + batch["x"] @ params["odd"][:64, :100]
    return jnp.mean((y - batch["y"]) ** 2) + jnp.mean(params["lanes"] ** 2), {}


def _toy_batches(n, k=None):
    rng = np.random.RandomState(0)
    lead = (B,) if k is None else (k, B)
    return [{"x": rng.randn(*lead, 64).astype(np.float32),
             "y": rng.randn(*lead, 100).astype(np.float32)} for _ in range(n)]


# ---- the plain form ---------------------------------------------------------


def _plain_reduce(part):
    return lambda grads, loss: (jax.lax.pmean(grads, "data"),
                                jax.lax.pmean(loss, "data"))


def _optimizers(name):
    """(the program's chain, the plain chain with optax's own clip)."""
    inner = {"adam": lambda: optax.adam(1e-2),
             "momentum": lambda: optax.sgd(0.1, momentum=0.9)}[name]
    return (make_optimizer(name, 1e-2 if name == "adam" else 0.1,
                           clip_norm=CLIP),
            optax.chain(optax.clip_by_global_norm(CLIP), inner()))


def _plain(monkeypatch_ctx, fn):
    """``fn()`` (build AND run: a step is traced at its first call) with
    every DP builder's reduction the plain one and no leaf large."""
    with monkeypatch_ctx.context() as m:
        for mod in (data_parallel, multistep, device_step):
            m.setattr(mod, "dp_reduce_fn", _plain_reduce)
        m.setattr(sharded_update, "MIN_SHARDED_BYTES", 2 ** 62)
        return fn()


def _replicated_state(state, mesh, *, stateful=False):
    """Whole parameters and moments on every chip (one chip shards
    nothing): where every DP state lived before."""
    spec = dp_state_spec(state, 1, stateful=stateful)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, jax.sharding.NamedSharding(mesh, s)),
        state, spec)


# ---- builders: build(optimizer, mesh) -> (step, the arguments of each
# dispatch after the state) ---------------------------------------------------


def _single(opt, mesh, **kw):
    step = make_dp_train_step(_toy_loss, opt, mesh, **kw)
    return step, [(shard_batch(b, mesh),) for b in _toy_batches(STEPS)]


def _multistep(opt, mesh):
    step = make_dp_multi_train_step(_toy_loss, opt, mesh)
    return step, [(shard_batch(b, mesh, dim=1),) for b in _toy_batches(2, K)]


def _grad_accum(opt, mesh):
    return _single(opt, mesh, grad_accum=2)


def _lm_loss(params, batch, rng, carries):
    return lm_loss(params, batch, CFG, carries=carries)


def _device_lm(opt, mesh):
    tokens = np.random.RandomState(0).randint(0, V, B * T * 9 + 1)
    data = stage_lm_data(tokens.astype(np.int32), B, T, mesh=mesh)
    step = make_device_dp_lm_train_step(
        _lm_loss, opt, data, mesh, steps_per_call=K, stateful=True)
    return step, [(data.arrays, np.int32(w)) for w in (0, K)]


def _toy_state(opt):
    return init_train_state(_toy_params(), opt, jax.random.PRNGKey(1))


def _lm_state(opt):
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), CFG))
    return init_train_state(params, opt, jax.random.PRNGKey(1),
                            carries=init_carries(CFG, B))


BUILDERS = {
    "single_step": (_single, _toy_state, False),
    "multistep_k4": (_multistep, _toy_state, False),
    "grad_accum2": (_grad_accum, _toy_state, False),
    "device_lm_stateful_k4": (_device_lm, _lm_state, True),
}


def _run(built, state, calls=slice(None)):
    step, args = built
    metrics = []
    for rest in args[calls]:
        state, m = step(state, *rest)
        metrics.append(jax.device_get(m))
    return jax.device_get(state), metrics


def _assert_close(a, b, rtol=2e-4, atol=2e-6):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        x, y, rtol=rtol, atol=atol), a, b)


@pytest.mark.parametrize("opt_name", ["adam", "momentum"])
@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_sharded_step_matches_the_replicated_one(
        builder, opt_name, small_leaves_qualify, monkeypatch):
    build, fresh, stateful = BUILDERS[builder]
    opt, plain_opt = _optimizers(opt_name)
    mesh = _mesh()

    state = place_dp_state(fresh(opt), mesh, stateful=stateful)
    for tree in (state.params, state.opt_state):
        specs = {x.sharding.spec for x in jax.tree.leaves(tree)}
        assert P("data") in specs or P(None, "data") in specs
    got, got_m = _run(build(opt, mesh), state)

    want, want_m = _plain(monkeypatch, lambda: _run(
        build(plain_opt, mesh),
        _replicated_state(fresh(plain_opt), mesh, stateful=stateful)))

    # the clip was at work, so a wrong norm would show in every leaf
    assert all(float(m["grad_norm"]) > CLIP for m in want_m)
    for g, w in zip(got_m, want_m):
        np.testing.assert_allclose(g["loss"], w["loss"], rtol=1e-5)
        np.testing.assert_allclose(g["grad_norm"], w["grad_norm"], rtol=1e-5)
        assert float(g["anomalous"]) == 0
    assert int(got.step) == int(want.step) in (STEPS, 2 * K)
    # device_get gathers a sharded leaf: whole, like the plain one's
    _assert_close(got.params, want.params)
    _assert_close(got.opt_state, want.opt_state)
    if stateful:
        _assert_close(got.carries, want.carries)


def test_fused_eval_reads_the_gathered_parameters(
        small_leaves_qualify, monkeypatch):
    """The eval pass inside the step's executable runs on whole parameters:
    the new shares, gathered in the eval branch."""
    mesh = _mesh()
    tokens = np.random.RandomState(0).randint(0, V, B * T * 9 + 1)
    data = stage_lm_data(tokens.astype(np.int32), B, T, mesh=mesh)
    valid = stage_lm_data(tokens[:B * T * 3 + 1].astype(np.int32), B, T,
                          mesh=mesh)
    carries = shard_batch(init_carries(CFG, B), mesh)

    def run(opt, state):
        step = make_device_dp_lm_train_step(
            _lm_loss, opt, data, mesh, eval_data=valid, steps_per_call=K,
            stateful=True)
        state, m = step(state, data.arrays, np.int32(0), valid.arrays,
                        np.bool_(True), carries)
        return jax.device_get((state.params, m["eval_loss"]))

    opt, plain_opt = _optimizers("adam")
    got = run(opt, place_dp_state(_lm_state(opt), mesh, stateful=True))
    want = _plain(monkeypatch, lambda: run(
        plain_opt, _replicated_state(_lm_state(plain_opt), mesh,
                                     stateful=True)))
    assert np.isfinite(got[1])
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5)
    _assert_close(got[0], want[0])


def test_nonfinite_gradient_on_one_shard_skips_the_update_on_all(
        small_leaves_qualify):
    opt, _ = _optimizers("adam")
    mesh = _mesh()
    step = make_dp_train_step(_toy_loss, opt, mesh, donate=False)
    good, bad = _toy_batches(2)
    bad["x"][B // DP * 2] = np.inf  # a row of the third shard alone
    state, _ = step(place_dp_state(_toy_state(opt), mesh, stateful=False),
                    shard_batch(good, mesh))
    after, m = step(state, shard_batch(bad, mesh))
    assert float(m["anomalous"]) == 1 and not np.isfinite(m["grad_norm"])
    assert int(after.step) == int(state.step) + 1

    def shards(tree):  # every chip's own copy or quarter, not device 0's
        return [np.asarray(s.data) for x in jax.tree.leaves(tree)
                for s in x.addressable_shards]

    for a, b in zip(shards((after.params, after.opt_state)),
                    shards((state.params, state.opt_state))):
        np.testing.assert_array_equal(a, b)
    # and the next good batch trains on
    again, m = step(after, shard_batch(good, mesh))
    assert float(m["anomalous"]) == 0
    assert not np.array_equal(np.asarray(again.params["rows"]),
                              np.asarray(after.params["rows"]))


def _collectives(text):
    return {k for k in ("reduce_scatter", "reduce-scatter", "all_gather",
                        "all-gather") if k in text}


@pytest.mark.parametrize("builder", ["single_step", "multistep_k4",
                                     "device_lm_stateful_k4"])
def test_no_qualifying_leaf_is_the_replicated_program(builder, monkeypatch):
    """At the module's own threshold no leaf of these models qualifies:
    the lowered text has neither collective of the sharded form, and the
    compiled program is the plain form's, instruction for instruction."""
    build, fresh, stateful = BUILDERS[builder]
    opt, plain_opt = _optimizers("adam")
    mesh = _mesh()
    assert sharded_share(fresh(opt).params, DP) == 0

    def texts(built, state):
        step, args = built
        lowered = step.lower(state, *args[0])
        return lowered.as_text(), lowered.compile().as_text()

    state = place_dp_state(fresh(opt), mesh, stateful=stateful)
    lowered, compiled = texts(build(opt, mesh), state)
    assert not _collectives(lowered), _collectives(lowered)
    assert "all-reduce" in compiled

    _, plain = _plain(monkeypatch, lambda: texts(
        build(plain_opt, mesh),
        _replicated_state(fresh(plain_opt), mesh, stateful=stateful)))
    assert _opcodes(compiled) == _opcodes(plain)


def _opcodes(hlo_text):
    """The compiled program as a multiset of (opcode, result shape)."""
    return collections.Counter(re.findall(
        r"= (\S+?)(?:\{[^}]*\})? ([a-z][a-z0-9-]*)\(", hlo_text))


@pytest.mark.parametrize("shape,itemsize,dp,want", [
    ((1024, 50000), 4, 4, 0),     # config 5's head: 12,500 lanes are no
                                  # multiple of 128; 256 rows are of 8
    ((50000, 1024), 4, 4, 1),     # its embedding: 256 lanes a chip
    ((50000, 1024), 4, 8, 1),     # 128 lanes a chip
    ((50000, 1024), 4, 16, None),  # 64 lanes: under a tile; 3,125 rows: odd
    ((50000, 1000), 4, 4, None),  # neither dimension divides
    ((1024, 1024), 4, 4, None),   # a layer's matrix: 4 MB, under the threshold
    ((4096, 4096), 4, 4, 1),      # 64 MB, at the threshold: the minor first
    ((4096, 4096), 2, 4, None),   # the same in bf16 is 32 MB
    ((8200, 8192), 2, 4, 1),
    ((8192, 8200), 2, 4, 0),      # bf16 tiles are 16 sublanes: 2,048 rows
    ((8200, 8200), 2, 4, None),   # 2,050 rows are not
    ((32 * MB,), 4, 4, None),     # a vector has no tile to keep whole
    ((1024, 50000), 4, 1, None),  # one chip: nothing to shard over
    ((6, 2048, 4100), 4, 4, 1),   # 512 rows
    ((8, 2052, 4100), 4, 4, 0),   # a leading dimension has no tile
])
def test_shard_dim_follows_shape_and_mesh_alone(shape, itemsize, dp, want):
    assert shard_dim(shape, itemsize, dp) == want


def test_indivisible_leaf_stays_replicated(small_leaves_qualify):
    opt, _ = _optimizers("adam")
    state = place_dp_state(_toy_state(opt), _mesh(), stateful=False)
    mu = state.opt_state[1][0].mu
    spec = {k: v.sharding.spec for k, v in mu.items()}
    assert spec == {"rows": P("data"), "lanes": P(None, "data"),
                    "odd": P(), "bias": P()}
    # a quarter a chip where sharded, the full logical shape as one array
    assert mu["lanes"].shape == (100, 512)
    assert mu["lanes"].addressable_shards[0].data.shape == (100, 128)
    assert mu["odd"].addressable_shards[0].data.shape == (100, 130)
    # a parameter lives as its moments do
    assert {k: v.sharding.spec for k, v in state.params.items()} == spec
    assert 0 < sharded_share(state.params, DP) < 100


# ---- checkpoints keep their shape -------------------------------------------


def _trained_sharded(tmp_path, opt):
    mesh = _mesh()
    state, _ = _run(_single(opt, mesh), place_dp_state(
        _toy_state(opt), mesh, stateful=False), slice(0, 2))
    ck = Checkpointer(str(tmp_path))
    # save from the placed (sharded) arrays, as the training loop does
    ck.save(place_dp_state(state, mesh, stateful=False))
    ck.wait()
    return ck, state


def test_sharded_checkpoint_restores_into_a_single_device_step(
        tmp_path, small_leaves_qualify):
    opt, _ = _optimizers("adam")
    ck, want = _trained_sharded(tmp_path, opt)
    restored = ck.restore_latest(_toy_state(opt))
    _assert_close(jax.device_get(restored), want, rtol=0, atol=0)
    step = make_train_step(_toy_loss, opt)
    state, m = step(restored, _toy_batches(3)[2])
    assert np.isfinite(float(m["loss"])) and int(state.step) == 3


def test_sharded_checkpoint_restores_onto_two_devices(
        tmp_path, small_leaves_qualify):
    opt, _ = _optimizers("adam")
    ck, want = _trained_sharded(tmp_path, opt)
    mesh = _mesh(2)
    template = place_dp_state(_toy_state(opt), mesh, stateful=False)
    restored = ck.restore_latest(template)
    assert (restored.opt_state[1][0].mu["lanes"].addressable_shards[0]
            .data.shape == (100, 256))
    _assert_close(jax.device_get(restored), want, rtol=0, atol=0)
    step = make_dp_train_step(_toy_loss, opt, mesh)
    state, m = step(restored, shard_batch(_toy_batches(3)[2], mesh))
    assert np.isfinite(float(m["loss"])) and int(state.step) == 3


def test_replicated_moments_checkpoint_restores_into_the_sharded_step(
        tmp_path, small_leaves_qualify, monkeypatch):
    """The layout every DP checkpoint had before: whole moments on every
    chip, written by the plain step."""
    opt, plain_opt = _optimizers("adam")
    mesh = _mesh()
    plain = lambda state, calls: _plain(monkeypatch, lambda: _run(  # noqa: E731
        _single(plain_opt, mesh), _replicated_state(state, mesh), calls))
    old, _ = plain(_toy_state(plain_opt), slice(0, 2))
    ck = Checkpointer(str(tmp_path))
    ck.save(_replicated_state(old, mesh))
    ck.wait()
    template = place_dp_state(_toy_state(opt), mesh, stateful=False)
    restored = ck.restore_latest(template)
    assert restored.opt_state[1][0].nu["rows"].sharding.spec == P("data")
    _assert_close(jax.device_get(restored), old, rtol=0, atol=0)
    # both go on from it alike
    got, _ = _run(_single(opt, mesh), restored, slice(2, 4))
    want, _ = plain(old, slice(2, 4))
    _assert_close(got.params, want.params)
    _assert_close(got.opt_state, want.opt_state)


# ---- donation and the gauge -------------------------------------------------


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_sharded_builders_donate_the_state_they_are_handed(
        builder, small_leaves_qualify):
    build, fresh, stateful = BUILDERS[builder]
    opt, _ = _optimizers("adam")
    mesh = _mesh()
    state = place_dp_state(fresh(opt), mesh, stateful=stateful)
    step, args = build(opt, mesh)
    for rest in args[:2]:
        handed = [x for x in jax.tree.leaves(state)
                  if isinstance(x, jax.Array)]
        state, _ = step(state, *rest)
        alive = [x.shape for x in handed if not x.is_deleted()]
        assert not alive, f"{len(alive)} of {len(handed)} leaves kept"


_CLI = """
import sys
from lstm_tensorspark_tpu.train import sharded_update
sharded_update.MIN_SHARDED_BYTES = 2048  # ptb_char's head: f32[32,50]
from lstm_tensorspark_tpu.cli import main
sys.exit(main(sys.argv[1:]))
"""


@pytest.mark.parametrize("partitions,sharded", [(4, True), (1, False)])
def test_cli_logs_the_sharded_share(partitions, sharded, tmp_path):
    """A child process: the multi-device CLI runs sometimes abort the
    interpreter in the final eval on a loaded machine (PERF.md §7), and
    the share is logged whatever the eval does."""
    jsonl = tmp_path / "metrics.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    subprocess.run(
        [sys.executable, "-c", _CLI, "--dataset", "ptb_char",
         "--hidden-units", "32", "--batch-size", "8", "--seq-len", "8",
         "--num-steps", "4", "--log-every", "2", "--compute-dtype", "float32",
         "--optimizer", "adam", "--learning-rate", "0.01",
         "--num-partitions", str(partitions), "--jsonl", str(jsonl)],
        env=env, check=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    records = [json.loads(line) for line in jsonl.read_text().splitlines()]
    (snapshot,) = [r for r in records if r.get("note") == "metrics_snapshot"]
    share = snapshot["dp_update_sharded_share"]
    assert (0 < share < 100) if sharded else share == 0
    assert snapshot["train_state_donated"] == 1
