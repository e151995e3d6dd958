"""ZeRO-1 optimizer-state sharding (parallel/zero.py) vs plain DP.

The law: the sliced-raveled update IS the leaf-wise update for elementwise
transforms, so a ZeRO-1 run must reproduce the replicated DP trajectory to
float-reassociation — while storing only 1/dp of the moments per shard.
Global-norm clipping is the non-elementwise case and is handled from the
psum'd norm; its parity against optax's in-chain clip is pinned separately.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.parallel import make_dp_train_step, make_mesh
from lstm_tensorspark_tpu.parallel.data_parallel import replicate, shard_batch
from lstm_tensorspark_tpu.parallel.zero import (
    make_zero1_opt_init,
    make_zero1_train_step,
)
from lstm_tensorspark_tpu.train import make_optimizer
from lstm_tensorspark_tpu.train.loop import init_train_state

V, H, B, T = 23, 16, 16, 12


def _setup(opt_name, lr, **opt_kw):
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2)
    # a host copy: the DP and the ZeRO-1 run both start from it, and each
    # step donates its own state
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), cfg))

    def loss_fn(p, b, r):
        return lm_loss(p, b, cfg)

    opt = make_optimizer(opt_name, lr, **opt_kw)
    mesh = make_mesh(dp=8)
    rng = np.random.RandomState(0)

    def batches(k):
        for _ in range(k):
            yield {
                "inputs": rng.randint(0, V, (B, T)).astype(np.int32),
                "targets": rng.randint(0, V, (B, T)).astype(np.int32),
            }

    return params, loss_fn, opt, mesh, batches


def _run_dp(params, loss_fn, opt, mesh, batches):
    step = make_dp_train_step(loss_fn, opt, mesh)
    state = init_train_state(params, opt, jax.random.PRNGKey(1))
    state = state._replace(params=replicate(state.params, mesh),
                           opt_state=replicate(state.opt_state, mesh))
    losses = []
    for b in batches:
        state, m = step(state, shard_batch(b, mesh))
        losses.append(float(m["loss"]))
    return state, losses


def _run_zero1(params, loss_fn, opt, mesh, batches, *, clip_norm=None):
    step = make_zero1_train_step(loss_fn, opt, mesh, clip_norm=clip_norm)
    state = init_train_state(params, opt, jax.random.PRNGKey(1))
    state = state._replace(
        params=replicate(state.params, mesh),
        opt_state=make_zero1_opt_init(opt, mesh)(
            replicate(params, mesh)),
    )
    losses = []
    for b in batches:
        state, m = step(state, shard_batch(b, mesh))
        losses.append(float(m["loss"]))
    return state, losses


@pytest.mark.parametrize("opt_name,lr", [("sgd", 0.5), ("adam", 1e-2)])
def test_zero1_matches_dp_trajectory(opt_name, lr):
    params, loss_fn, opt, mesh, batches = _setup(opt_name, lr)
    s_dp, l_dp = _run_dp(params, loss_fn, opt, mesh, list(batches(5)))

    params2, loss_fn2, opt2, mesh2, batches2 = _setup(opt_name, lr)
    s_z, l_z = _run_zero1(params2, loss_fn2, opt2, mesh2, list(batches2(5)))

    np.testing.assert_allclose(l_z, l_dp, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
        jax.device_get(s_z.params), jax.device_get(s_dp.params),
    )


def test_zero1_clip_matches_optax_chain_clip():
    """ZeRO-1's psum-norm clipping == optax.clip_by_global_norm in the DP
    chain, at a learning rate/scale where clipping actually engages."""
    clip = 0.05  # global grad norm at init is well above this
    params, loss_fn, opt_clip, mesh, batches = _setup(
        "sgd", 0.5, clip_norm=clip)
    s_dp, l_dp = _run_dp(params, loss_fn, opt_clip, mesh, list(batches(4)))

    params2, loss_fn2, _, mesh2, batches2 = _setup("sgd", 0.5)
    opt_noclip = make_optimizer("sgd", 0.5)  # clip handled by zero1
    s_z, l_z = _run_zero1(params2, loss_fn2, opt_noclip, mesh2,
                          list(batches2(4)), clip_norm=clip)

    np.testing.assert_allclose(l_z, l_dp, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
        jax.device_get(s_z.params), jax.device_get(s_dp.params),
    )


def test_zero1_opt_state_is_sharded_one_over_dp():
    """Adam moments live 1/dp per shard: the global vector leaves have the
    padded flat length and are sharded P(\"data\"); plain DP replicates the
    full pytree on every shard."""
    params, _, opt, mesh, _ = _setup("adam", 1e-2)
    opt_state = make_zero1_opt_init(opt, mesh)(replicate(params, mesh))

    n = sum(int(np.size(a)) for a in jax.tree.leaves(params))
    dp = mesh.shape["data"]
    chunk = -(-n // dp)

    vec_leaves = [a for a in jax.tree.leaves(opt_state)
                  if getattr(a, "ndim", 0) == 1]
    assert vec_leaves, "adam state should contain mu/nu vectors"
    for leaf in vec_leaves:
        assert leaf.shape == (dp * chunk,)
        # each process-local shard holds chunk elements, not dp*chunk
        shard_shapes = {s.data.shape for s in leaf.addressable_shards}
        assert shard_shapes == {(chunk,)}


@pytest.mark.parametrize("n_extra", [0, 1, 7])
def test_zero1_padding_edges(n_extra):
    """The raveled length may or may not divide dp: exercise exact-divide
    (pad=0) and maximal-pad layouts with a tiny synthetic param pytree and
    assert trajectory parity with plain DP."""
    dp = 8
    mesh = make_mesh(dp=dp)
    # base 16*dp params + n_extra => pad = (-n_extra) % dp
    sizes = [16 * dp, n_extra] if n_extra else [16 * dp]
    keys = jax.random.split(jax.random.PRNGKey(7), len(sizes))
    params = jax.device_get(
        {f"w{i}": jax.random.normal(k, (s,), jnp.float32)
         for i, (s, k) in enumerate(zip(sizes, keys))})

    xs = jax.random.normal(jax.random.PRNGKey(8), (B, sum(sizes)), jnp.float32)

    def loss_fn(p, batch, r):
        flat = jnp.concatenate([p[k] for k in sorted(p)])
        pred = batch @ flat
        return jnp.mean(pred ** 2), {"loss": None, "carries": None}

    opt = make_optimizer("adam", 1e-2)
    batches = [xs] * 3

    s_dp, _ = _run_dp(params, loss_fn, opt, mesh, batches)
    s_z, _ = _run_zero1(params, loss_fn, opt, mesh, batches)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
        jax.device_get(s_z.params), jax.device_get(s_dp.params),
    )


def test_zero1_multistep_matches_single_dispatch():
    """K-step ZeRO-1 (scan inside the shard_map) == K single dispatches:
    same final params, and the summarized metrics follow the multi-step
    contract (mean loss over K, final grad_norm)."""
    params, loss_fn, opt, mesh, batches = _setup("adam", 1e-2)
    bs = list(batches(4))

    s_one, l_one = _run_zero1(params, loss_fn, opt, mesh, bs)

    step_k = make_zero1_train_step(loss_fn, opt, mesh, steps_per_call=4)
    state = init_train_state(params, opt, jax.random.PRNGKey(1))
    state = state._replace(
        params=replicate(state.params, mesh),
        opt_state=make_zero1_opt_init(opt, mesh)(replicate(params, mesh)),
    )
    stacked = jax.tree.map(lambda *a: np.stack(a), *bs)
    state, m = step_k(state, shard_batch(stacked, mesh, dim=1))

    np.testing.assert_allclose(float(m["loss"]), np.mean(l_one),
                               rtol=1e-5, atol=1e-6)
    assert "loss_last" in m
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
        jax.device_get(state.params), jax.device_get(s_one.params),
    )
    assert int(jax.device_get(state.step)) == 4


# ---------------------------------------------------------------------------
# GSPMD ZeRO-1 x tensor parallelism (zero1_tp_opt_specs): the TP task
# runners' form — moment leaves sharded over data AND model, trajectory
# identical to the plain TP step, no clip special-casing.
# ---------------------------------------------------------------------------


def _tp_setup(zero1: bool, *, clip=None):
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from lstm_tensorspark_tpu.models import (
        ClassifierConfig, classifier_loss, init_classifier,
    )
    from lstm_tensorspark_tpu.parallel.tensor_parallel import (
        classifier_param_specs, make_tp_train_step, place_params,
    )
    from lstm_tensorspark_tpu.parallel.zero import zero1_tp_opt_specs

    cfg = ClassifierConfig(vocab_size=V, hidden_size=H, num_layers=1)
    params = init_classifier(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adam", 1e-2, clip_norm=clip)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    specs = classifier_param_specs(params)

    def loss_fn(p, b, r):
        return classifier_loss(p, b, cfg)

    state = init_train_state(params, opt, jax.random.PRNGKey(1))
    state = state._replace(params=place_params(state.params, specs, mesh))
    opt_specs = None
    if zero1:
        opt_specs = zero1_tp_opt_specs(opt, params, specs, mesh)
        state = state._replace(
            opt_state=place_params(state.opt_state, opt_specs, mesh))
    step = make_tp_train_step(loss_fn, opt, mesh, params, param_specs=specs,
                              opt_state_specs=opt_specs)
    rng = np.random.RandomState(7)

    def batches(k):
        for _ in range(k):
            yield {
                "tokens": rng.randint(0, V, (B, T)).astype(np.int32),
                "lengths": np.full((B,), T, np.int32),
                "labels": rng.randint(0, 2, (B,)).astype(np.int32),
                "valid": np.ones((B,), np.float32),
            }

    return state, step, batches, opt_specs


@pytest.mark.parametrize("clip", [None, 0.5])
def test_zero1_tp_matches_plain_tp_trajectory(clip):
    """Same batches, same seed: the data-sharded-moments step must walk the
    exact trajectory of the propagation-sharded step — the annotation moves
    MEMORY, not math. Clipping needs no special casing here (grads are
    logically replicated over data), so it rides along unchanged."""
    out = {}
    for zero1 in (False, True):
        state, step, batches, _ = _tp_setup(zero1, clip=clip)
        losses = []
        for b in batches(5):
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        out[zero1] = (losses, state)
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
        out[True][1].params, out[False][1].params,
    )


def test_zero1_tp_moments_shard_over_data_and_model():
    """The published memory claim: after a step, every matrix moment leaf
    is sharded over BOTH axes (1/(dp*tp) per device), and the output state
    PRESERVES it (the out_shardings pin — propagation alone would undo it)."""
    from jax.sharding import PartitionSpec as P

    from jax.tree_util import GetAttrKey, tree_flatten_with_path

    state, step, batches, opt_specs = _tp_setup(True)
    for b in batches(2):
        state, _ = step(state, b)
    leaves = tree_flatten_with_path(state.opt_state)[0]
    mats = [a for path, a in leaves
            if GetAttrKey("mu") in path and a.ndim == 2]
    assert mats, "expected matrix moment leaves under .mu"
    both = 0
    for a in mats:
        spec = a.sharding.spec
        # every matrix moment picks up the data axis; the TP-sharded cell
        # kernels keep the model axis too -> 1/(dp*tp) per device
        assert "data" in spec, spec
        if "model" in spec:
            both += 1
            shard = a.addressable_shards[0].data
            assert shard.size * 4 == a.size, (shard.shape, a.shape)
    assert both >= 16, f"cell kernels should shard over both axes ({both})"
    # scalar leaves (adam's count) stay replicated
    counts = [a for path, a in leaves
              if GetAttrKey("count") in path]
    assert counts and all(c.sharding.spec == P() for c in counts)


def test_zero1_tp_specs_suffix_matching_is_shape_guarded():
    """Path-suffix matching must not mis-bind a moment leaf whose suffix
    matches a param path with a DIFFERENT shape; unmatched/scalar leaves
    stay replicated."""
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    from lstm_tensorspark_tpu.parallel.zero import zero1_tp_opt_specs

    params = {"a": {"b": jnp.zeros((8, 8))}, "b": jnp.zeros((4,))}
    specs = {"a": {"b": P(None, "model")}, "b": P()}
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    out = zero1_tp_opt_specs(optax.adam(1e-3), params, specs, mesh)
    mu = out[0].mu
    # ['a']['b'] ends with ('b',) too, but shape 8x8 != (4,): the longer
    # exact match must win and carry the model axis forward
    assert mu["a"]["b"] == P("data", "model")
    assert mu["b"] == P("data")
    assert out[0].count == P()


def test_zero1_tp_specs_reject_malformed_inputs():
    """Hardening: a spec tree with the wrong leaf count must error (zip
    would silently mispair), and an optimizer whose state mirrors nothing
    (factored accumulators) must refuse rather than pin everything
    replicated — which would use MORE memory than plain propagation."""
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    from lstm_tensorspark_tpu.parallel.zero import zero1_tp_opt_specs

    params = {"a": jnp.zeros((8, 8)), "b": jnp.zeros((4,))}
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    with pytest.raises(ValueError, match="mirror"):
        zero1_tp_opt_specs(optax.adam(1e-3), params, {"a": P()}, mesh)
    # same leaf COUNT but a typoed key: positional zip would mispair
    # silently; the path-keyed pairing must refuse
    with pytest.raises(ValueError, match="mirror"):
        zero1_tp_opt_specs(optax.adam(1e-3), params,
                           {"a": P(None, "model"), "z": P()}, mesh)
    specs = {"a": P(None, "model"), "b": P()}
    # a factored-accumulator-style state (nothing mirrors the params):
    # refusal, not a silent all-replicated pin
    factored = optax.GradientTransformation(
        init=lambda p: {"acc": jnp.zeros((3,))},
        update=lambda g, s, p=None: (g, s),
    )
    with pytest.raises(ValueError, match="mirrors the params"):
        zero1_tp_opt_specs(factored, params, specs, mesh)


def test_zero1_tp_checkpoint_reshards_across_mesh_shapes(tmp_path):
    """The docs claim GSPMD ZeRO-1 checkpoints reshard across ANY later
    dp x tp (full logical shapes — unlike the ravel form's padded-flat
    contract). Back it: train on dp2 x tp2, checkpoint, restore onto a
    dp4 x tp1 mesh AND onto a plain single-device state; continuing on
    either must match the uninterrupted dp2 x tp2 run step-for-step."""
    import numpy as np
    from jax.sharding import Mesh

    from lstm_tensorspark_tpu.models import (
        ClassifierConfig, classifier_loss, init_classifier,
    )
    from lstm_tensorspark_tpu.parallel.tensor_parallel import (
        classifier_param_specs, make_tp_train_step, place_params,
    )
    from lstm_tensorspark_tpu.parallel.zero import zero1_tp_opt_specs
    from lstm_tensorspark_tpu.train import make_train_step
    from lstm_tensorspark_tpu.train.checkpoint import Checkpointer

    cfg = ClassifierConfig(vocab_size=V, hidden_size=H, num_layers=1)
    params = init_classifier(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adam", 1e-2)
    specs = classifier_param_specs(params)

    rng = np.random.RandomState(3)
    bs = [{
        "tokens": rng.randint(0, V, (B, T)).astype(np.int32),
        "lengths": np.full((B,), T, np.int32),
        "labels": rng.randint(0, 2, (B,)).astype(np.int32),
        "valid": np.ones((B,), np.float32),
    } for _ in range(4)]

    def build(mesh_shape):
        mesh = Mesh(np.asarray(jax.devices()[: np.prod(mesh_shape)])
                    .reshape(mesh_shape), ("data", "model"))
        opt_specs = zero1_tp_opt_specs(opt, params, specs, mesh)
        step = make_tp_train_step(
            lambda p, b, r: classifier_loss(p, b, cfg), opt, mesh, params,
            param_specs=specs, opt_state_specs=opt_specs, donate=False)
        st = init_train_state(params, opt, jax.random.PRNGKey(1))
        return mesh, opt_specs, step, st._replace(
            params=place_params(st.params, specs, mesh),
            opt_state=place_params(st.opt_state, opt_specs, mesh))

    # uninterrupted dp2 x tp2 reference over all 4 batches
    _, _, step_a, st = build((2, 2))
    ref = st
    losses_ref = []
    for b in bs:
        ref, m = step_a(ref, b)
        losses_ref.append(float(m["loss"]))

    # train 2 steps, checkpoint the SHARDED state (st is untouched by the
    # functional reference loop above — no second build needed)
    st2 = st
    for b in bs[:2]:
        st2, _ = step_a(st2, b)
    ckpt = Checkpointer(str(tmp_path / "ck"))
    ckpt.save(st2)

    # (a) restore onto dp4 x tp1 and continue there
    mesh_b, opt_specs_b, step_b, fresh_b = build((4, 1))
    restored = ckpt.restore_latest(fresh_b)
    restored = restored._replace(
        params=place_params(restored.params, specs, mesh_b),
        opt_state=place_params(restored.opt_state, opt_specs_b, mesh_b))
    out_b = []
    for b in bs[2:]:
        restored, m = step_b(restored, b)
        out_b.append(float(m["loss"]))
    np.testing.assert_allclose(out_b, losses_ref[2:], rtol=1e-5, atol=1e-6)

    # (b) restore onto a plain unsharded single-device state and continue
    fresh_c = init_train_state(params, opt, jax.random.PRNGKey(1))
    restored_c = ckpt.restore_latest(fresh_c)
    step_c = make_train_step(
        lambda p, b, r: classifier_loss(p, b, cfg), opt)
    out_c = []
    for b in bs[2:]:
        restored_c, m = step_c(restored_c, b)
        out_c.append(float(m["loss"]))
    np.testing.assert_allclose(out_c, losses_ref[2:], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("model", ["seq2seq", "lm"])
def test_zero1_tp_other_model_families(model):
    """The spec derivation is model-agnostic: the seq2seq tree (encoder and
    decoder layer-0 cells have IDENTICAL shapes at different paths — the
    full-path-suffix match must keep them apart) and the LM via the
    library-level GSPMD TP step (the CLI's LM TP is the manual {data,seq}
    form and rejects --zero1, but make_tp_train_step's default
    lm_param_specs composes fine). Trajectory must match the plain TP step."""
    from jax.sharding import Mesh

    from lstm_tensorspark_tpu.parallel.tensor_parallel import (
        lm_param_specs, make_tp_train_step, place_params,
        seq2seq_param_specs,
    )
    from lstm_tensorspark_tpu.parallel.zero import zero1_tp_opt_specs

    rng = np.random.RandomState(5)
    if model == "seq2seq":
        from lstm_tensorspark_tpu.models import (
            Seq2SeqConfig, init_seq2seq, seq2seq_loss,
        )

        cfg = Seq2SeqConfig(num_features=6, hidden_size=H, num_layers=2,
                            horizon=4)
        params = init_seq2seq(jax.random.PRNGKey(0), cfg)
        specs = seq2seq_param_specs(params)
        loss = lambda p, b, r: seq2seq_loss(p, b, cfg)  # noqa: E731
        batches = [{
            "context": rng.randn(B, 10, 6).astype(np.float32),
            "targets": rng.randn(B, 4, 6).astype(np.float32),
        } for _ in range(4)]
    else:
        cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=2)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        specs = lm_param_specs(params)
        loss = lambda p, b, r: lm_loss(p, b, cfg)  # noqa: E731
        batches = [{
            "inputs": rng.randint(0, V, (B, T)).astype(np.int32),
            "targets": rng.randint(0, V, (B, T)).astype(np.int32),
        } for _ in range(4)]

    opt = make_optimizer("adam", 1e-2)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    out = {}
    for zero1 in (False, True):
        opt_specs = (zero1_tp_opt_specs(opt, params, specs, mesh)
                     if zero1 else None)
        step = make_tp_train_step(loss, opt, mesh, params, param_specs=specs,
                                  opt_state_specs=opt_specs, donate=False)
        st = init_train_state(params, opt, jax.random.PRNGKey(1))
        st = st._replace(params=place_params(st.params, specs, mesh))
        if zero1:
            st = st._replace(
                opt_state=place_params(st.opt_state, opt_specs, mesh))
        losses = []
        for b in batches:
            st, m = step(st, b)
            losses.append(float(m["loss"]))
        out[zero1] = (losses, st)
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
        out[True][1].params, out[False][1].params,
    )
