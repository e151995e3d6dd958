"""The two head + loss functions of ops/xent.py against the plain
logsumexp loss and `jax.grad` of it — values AND gradients: the
vocab-chunked one (padded-V and tied-head cases) and the dense one with
its hand-written backward (float32 and bf16 logits, tied head, row counts
off the tile grid, the word-LM vocabularies)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lstm_tensorspark_tpu.ops import pallas_xent
from lstm_tensorspark_tpu.ops.xent import chunked_xent_mean, dense_xent_mean

B, T, H, V = 4, 6, 16, 37  # V deliberately off the chunk grid


def _ref_loss(ys, kernel, bias, targets, ldtype=jnp.float32):
    """The plain loss, as lm_loss's dense branch computed it before
    ops/xent.py took it over: logits in ``ldtype``, logsumexp - target in
    float32, gradients by autodiff."""
    logits = (
        jnp.dot(ys.astype(kernel.dtype), kernel, preferred_element_type=ldtype)
        + bias.astype(ldtype)
    ).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def _setup(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    ys = jax.random.normal(ks[0], (B, T, H))
    kernel = jax.random.normal(ks[1], (H, V)) * 0.3
    bias = jax.random.normal(ks[2], (V,)) * 0.1
    targets = jax.random.randint(ks[3], (B, T), 0, V)
    return ys, kernel, bias, targets


def test_value_matches_reference():
    ys, kernel, bias, targets = _setup()
    for chunk in (8, 16, 64):  # multiple tiles / pad-only / single tile
        got = chunked_xent_mean(ys, kernel, bias, targets, chunk)
        np.testing.assert_allclose(
            float(got), float(_ref_loss(ys, kernel, bias, targets)),
            rtol=1e-6,
        )


def test_grads_match_reference():
    ys, kernel, bias, targets = _setup(seed=1)
    g1 = jax.grad(
        lambda y, k, b: chunked_xent_mean(y, k, b, targets, 8),
        argnums=(0, 1, 2),
    )(ys, kernel, bias)
    g2 = jax.grad(
        lambda y, k, b: _ref_loss(y, k, b, targets), argnums=(0, 1, 2)
    )(ys, kernel, bias)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
        g1, g2,
    )


def test_under_jit_and_value_and_grad():
    ys, kernel, bias, targets = _setup(seed=2)
    f = jax.jit(jax.value_and_grad(
        lambda y, k, b, t: chunked_xent_mean(y, k, b, t, 16),
        argnums=(0, 1, 2),
    ))
    v, g = f(ys, kernel, bias, targets)
    np.testing.assert_allclose(
        float(v), float(_ref_loss(ys, kernel, bias, targets)), rtol=1e-6
    )
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(g))


def test_lm_loss_big_v_parity(monkeypatch):
    """lm_loss's big-V path (auto-selected above _CHUNKED_XENT_MIN_V) must
    match a hand-computed plain loss on the same params — including
    gradients through the whole model. The threshold is lowered for the
    test so the parity check stays cheap (the real threshold targets
    vocabularies whose logits would not fit HBM)."""
    import lstm_tensorspark_tpu.models.lstm_lm as lm_mod
    from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_forward, lm_loss

    monkeypatch.setattr(lm_mod, "_CHUNKED_XENT_MIN_V", 4096)
    V_big = 4109
    cfg = LMConfig(vocab_size=V_big, hidden_size=16, num_layers=1)
    params = init_lm(jax.random.PRNGKey(3), cfg)
    data = jax.random.randint(jax.random.PRNGKey(4), (B, T + 1), 0, V_big)
    batch = {"inputs": data[:, :-1], "targets": data[:, 1:]}

    def plain(p):
        logits, _ = lm_forward(p, batch["inputs"], cfg)
        lg = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, batch["targets"][..., None],
                                  axis=-1)[..., 0]
        return jnp.mean(lse - tgt)

    def chunked(p):
        return lm_loss(p, batch, cfg)[0]

    np.testing.assert_allclose(float(chunked(params)), float(plain(params)),
                               rtol=1e-6)
    g1 = jax.grad(chunked)(params)
    g2 = jax.grad(plain)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7),
        g1, g2,
    )


# ---- dense_xent_mean: the hand-written backward vs jax.grad --------------


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# (V, logits dtype, (B, T), tied head, under jit). N = B*T is off the
# 8-row tile grid in the (3, 5) and (3, 3) cases.
DENSE_CASES = [
    (89, "float32", (4, 6), False, False),
    (89, "float32", (3, 5), False, True),
    (89, "bfloat16", (4, 6), False, True),
    (89, "float32", (4, 6), True, True),
    (89, "bfloat16", (3, 5), True, False),
    (1000, "float32", (3, 5), False, True),
    (1000, "bfloat16", (4, 6), False, True),
    (1000, "float32", (4, 6), True, True),
    (33278, "float32", (2, 4), False, True),
    (33278, "bfloat16", (3, 3), False, True),
    (33278, "float32", (3, 3), True, True),
]


@pytest.mark.parametrize("V,ldtype,shape,tied,jit", DENSE_CASES)
def test_dense_matches_plain_value_and_grads(V, ldtype, shape, tied, jit):
    (Bn, Tn), Hn = shape, 16
    ldtype = jnp.dtype(ldtype)
    ks = jax.random.split(jax.random.PRNGKey(V + Bn), 4)
    ys = jax.random.normal(ks[0], (Bn, Tn, Hn))
    # tied: the head kernel is the embedding's transpose, so the kernel's
    # gradient must come back through the transpose in the embedding's shape
    weight = jax.random.normal(ks[1], (V, Hn) if tied else (Hn, V)) * 0.3
    bias = jax.random.normal(ks[2], (V,)) * 0.1
    targets = jax.random.randint(ks[3], (Bn, Tn), 0, V)
    kernel_of = (lambda w: w.T) if tied else (lambda w: w)

    def dense(y, w, b):
        return dense_xent_mean(y, kernel_of(w), b, targets, ldtype)

    def plain(y, w, b):
        return _ref_loss(y, kernel_of(w), b, targets, ldtype)

    wrap = jax.jit if jit else (lambda f: f)
    got_v, got_g = wrap(jax.value_and_grad(dense, argnums=(0, 1, 2)))(
        ys, weight, bias)
    want_v, want_g = wrap(jax.value_and_grad(plain, argnums=(0, 1, 2)))(
        ys, weight, bias)
    # float32: the same sums in another order. bf16 logits: dlogits is
    # rounded to bf16 once in both, from float32 values an ulp apart
    tol = 1e-5 if ldtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(float(got_v), float(want_v), rtol=1e-6)
    for got, want in zip(got_g, want_g):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert _rel_l2(got, want) < tol


@pytest.mark.parametrize("kw", [
    dict(),
    dict(num_layers=2),
    dict(tie_embeddings=True),
    dict(logits_dtype="bfloat16"),
    dict(logits_dtype="bfloat16", compute_dtype="bfloat16", dropout=0.3),
], ids=["f32", "two_layers", "tied", "bf16_logits", "bf16_dropout"])
def test_lm_loss_is_what_it_was(kw):
    """lm_loss on a seeded batch against the dense branch as it was
    (lm_forward, logsumexp - target, autodiff): loss, carries and every
    parameter's gradient; float32 to rounding."""
    from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_forward, lm_loss

    Vn = 211
    cfg = LMConfig(vocab_size=Vn, hidden_size=16, **kw)
    params = init_lm(jax.random.PRNGKey(5), cfg)
    data = jax.random.randint(jax.random.PRNGKey(6), (5, T + 1), 0, Vn)
    batch = {"inputs": data[:, :-1], "targets": data[:, 1:]}
    rng = jax.random.PRNGKey(7) if cfg.dropout else None

    def before(p):
        logits, finals = lm_forward(p, batch["inputs"], cfg, dropout_rng=rng,
                                    deterministic=rng is None)
        lg = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, batch["targets"][..., None],
                                  axis=-1)[..., 0]
        return jnp.mean(lse - tgt), finals

    def now(p):
        loss, aux = lm_loss(p, batch, cfg, dropout_rng=rng,
                            deterministic=rng is None)
        return loss, (aux["carries"], aux["tokens"])

    (want_v, want_c), want_g = jax.jit(
        jax.value_and_grad(before, has_aux=True))(params)
    (got_v, (got_c, tokens)), got_g = jax.jit(
        jax.value_and_grad(now, has_aux=True))(params)
    assert float(tokens) == batch["targets"].size
    np.testing.assert_allclose(float(got_v), float(want_v), rtol=1e-6)
    jax.tree.map(np.testing.assert_array_equal, got_c, want_c)
    # bf16 logits: autodiff summed the bias gradient from the ROUNDED
    # dlogits, in bf16 (1.4% off the float32-logits gradient here); it is
    # now summed in float32 before the rounding (0.00%), so the two differ
    # by what the old one was off
    tol = 1e-5 if cfg.ldtype == jnp.float32 else 3e-2
    jax.tree.map(lambda a, b: np.testing.assert_array_less(_rel_l2(a, b), tol),
                 got_g, want_g)


# ---- dense_xent_mean through the Pallas kernels (ops/pallas_xent.py) -----
# Interpret mode on the CPU, small tiles so that every case has two row
# tiles in both kernels, two row steps a tile and several V tiles with a
# ragged last one. The kernels feed the MXU bf16, so the reference
# is the plain loss on the bf16-rounded hidden states and head.

KH = 128  # the kernels want the MXU's 128 lanes
SMALL = pallas_xent.Plan(pallas_xent.Tiles(32, 128, 16),
                         pallas_xent.Tiles(32, 256, 16), interpret=True)


@pytest.fixture
def head_kernels(monkeypatch):
    """Steer `dense_xent_mean` onto the kernels here, in the test: the
    plan's rules as on a TPU, the small tiles above, interpreted."""
    real = pallas_xent.plan

    def plan(*args, **kwargs):
        p = real(*args, **{**kwargs, "platform": "tpu"})
        return None if p is None else SMALL

    monkeypatch.setattr(pallas_xent, "plan", plan)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


# (V, logits dtype, tied head, g). Every V ends inside a 128-column tile: a
# head whose V fills its tiles is stored row-major and keeps XLA's path
KERNEL_CASES = [
    (300, "bfloat16", False, 1.0),
    (300, "float32", False, 1.0),
    (300, "bfloat16", True, 1.0),
    (300, "float32", True, 2.5),
    (520, "bfloat16", False, 0.3),
    (1000, "bfloat16", False, 1.0),
    (1000, "float32", True, -1.5),
]


@pytest.mark.parametrize("V,ldtype,tied,g", KERNEL_CASES)
def test_dense_kernels_match_plain_value_and_grads(head_kernels, V, ldtype,
                                                  tied, g):
    Bn, Tn = 4, 16  # N = 64: two row tiles in each kernel
    ldtype = jnp.dtype(ldtype)
    ks = jax.random.split(jax.random.PRNGKey(V), 4)
    ys = _bf16(jax.random.normal(ks[0], (Bn, Tn, KH)))
    weight = _bf16(jax.random.normal(ks[1], (V, KH) if tied else (KH, V))
                   * 0.3)
    bias = jax.random.normal(ks[2], (V,)) * 0.1
    # targets in the ragged last tile too, its last column among them
    targets = jax.random.randint(ks[3], (Bn, Tn), 0, V)
    targets = targets.at[0, :3].set(jnp.array([V - 1, V - 2, V - 40]))
    kernel_of = (lambda w: w.T) if tied else (lambda w: w)

    def dense(y, w, b):
        return g * dense_xent_mean(y, kernel_of(w), b, targets, ldtype)

    def plain(y, w, b):
        return g * _ref_loss(y, kernel_of(w), b, targets, ldtype)

    step = jax.jit(jax.value_and_grad(dense, argnums=(0, 1, 2)))
    assert "lm_head_fwd" in str(jax.make_jaxpr(step)(ys, weight, bias))
    assert "lm_head_dx" in str(jax.make_jaxpr(step)(ys, weight, bias))
    got_v, got_g = step(ys, weight, bias)
    # op by op: under jit XLA may add the bias before it rounds the product
    # (excess precision), which the kernel, as XLA's TPU fusion, does not
    want_v, want_g = jax.value_and_grad(plain, argnums=(0, 1, 2))(
        ys, weight, bias)
    np.testing.assert_allclose(float(got_v), float(want_v), rtol=1e-5)
    # lse and the target logit, as the forward kernel hands them on
    ys2d = jnp.swapaxes(ys, 0, 1).reshape(-1, KH)
    tgt = targets.T.reshape(-1)
    wt = kernel_of(weight).T
    logits, lse, tl = pallas_xent.lm_head_fwd(ys2d, wt, bias, tgt, ldtype,
                                              SMALL)
    # the stored logits to the rounding of their dtype (the sums run in
    # another order), lse and the target logit of the stored values
    want = (ys2d @ wt.T).astype(ldtype) + bias.astype(ldtype)
    ulp = 2.0 ** -7 if ldtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(want, np.float32), rtol=ulp,
                               atol=ulp)
    stored = logits.astype(jnp.float32)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(stored, axis=-1),
                               rtol=1e-6)
    np.testing.assert_array_equal(tl, stored[jnp.arange(tgt.size), tgt])
    # dys: dlogits rounded to bf16 for the MXU (and dys to the logits'
    # dtype); dW from the same rounded dlogits
    for got, want in zip(got_g, want_g):
        assert got.shape == want.shape and got.dtype == want.dtype
    assert _rel_l2(got_g[0], want_g[0]) < 1e-2
    assert _rel_l2(got_g[1], want_g[1]) < 1e-2
    # db: float32 dlogits of the stored logits, summed before any rounding
    # (autodiff of bf16 logits sums them rounded, in bf16: 1% off)
    dlog = jax.nn.softmax(stored, axis=-1) - jax.nn.one_hot(tgt, V)
    assert _rel_l2(got_g[2], g / tgt.size * jnp.sum(dlog, axis=0)) < 1e-5


def _lowered_head(ys, kernel, bias, targets, *, h_platform="tpu"):
    """The jaxpr of value_and_grad of `dense_xent_mean` with the plan asked
    as on ``h_platform``."""
    def f(y, k, b):
        return dense_xent_mean(y, k, b, targets, jnp.bfloat16)
    return str(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(
        ys, kernel, bias))


@pytest.mark.parametrize("case", ["tpu", "cpu", "width_off_lanes",
                                  "head_stored_row_major", "rows_off_tiles",
                                  "sharded_vocab", "automatic_axis_of_one",
                                  "every_axis_manual"])
def test_dense_head_takes_the_kernels_only_where_it_may(monkeypatch, case):
    """One algorithm with a shape rule: the kernels where the plan says so;
    XLA's operations on another backend, at a width off the 128 lanes, for
    a head the TPU stores row-major (V = 256 fills its tiles: the kernels'
    [V, H] view would copy it), at rows no tile divides, and under a
    ``shard_map`` that leaves a mesh axis automatic: the tensor-parallel
    step's head is sharded over V there, and Mosaic lowers no kernel under
    an automatic axis even of one device (the sequence- and
    pipeline-parallel steps without ``use_pallas``). A ``shard_map`` that
    makes every axis manual takes the kernels."""
    real = pallas_xent.plan
    if case != "cpu":
        monkeypatch.setattr(pallas_xent, "plan", lambda *a, **k: real(
            *a, **{**k, "platform": "tpu"}))
    h = 100 if case == "width_off_lanes" else KH
    B_, T_ = (3, 5) if case == "rows_off_tiles" else (4, 16)
    v = 256 if case == "head_stored_row_major" else 300
    ys = jnp.ones((B_, T_, h))
    kernel, bias = jnp.ones((h, v)), jnp.zeros((v,))
    targets = jnp.zeros((B_, T_), jnp.int32)
    meshes = {"sharded_vocab": ((2, 2), {"data"}),
              "automatic_axis_of_one": ((4, 1), {"data"}),
              "every_axis_manual": ((4, 1), {"data", "model"})}
    if case in meshes:
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        shape, manual = meshes[case]
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(shape),
                    ("data", "model"))
        text = str(jax.make_jaxpr(shard_map(
            lambda y, k, b, t: jax.value_and_grad(
                lambda y, k, b: dense_xent_mean(y, k, b, t, jnp.bfloat16),
                argnums=(0, 1, 2))(y, k, b),
            mesh=mesh, in_specs=(P("data"), P(), P(), P("data")),
            out_specs=P(), axis_names=manual, check_vma=False))(
                ys, kernel, bias, targets))
    else:
        text = _lowered_head(ys, kernel, bias, targets)
    engaged = "lm_head_fwd" in text, "lm_head_dx" in text
    want = case in ("tpu", "every_axis_manual")
    assert engaged == ((True, True) if want else (False, False))


# (mesh axes and sizes, the axes made manual, head shape): whether the
# kernels run
MESH_CASES = {
    # one chip, no mesh: the head read as stored
    "no_mesh": (None, None, (1024, 50_000), True),
    # the data-parallel step gathers config 5's head (it lives a quarter a
    # chip): XLA's operations, faster there
    "dp_gathered_head": ({"data": 4}, None, (1024, 50_000), False),
    # ... and a head below its threshold stays whole there
    "dp_whole_head": ({"data": 4}, None, (128, 300), True),
    # the sequence-parallel step's head is replicated, never gathered
    "sp_replicated_head": ({"data": 2, "seq": 2}, None, (1024, 50_000),
                           True),
    # a manual data axis of one device holds nothing sharded
    "data_axis_of_one": ({"data": 1, "model": 4}, None, (1024, 50_000),
                         True),
    # an automatic axis: Mosaic lowers no kernel
    "automatic_axis": ({"data": 2, "model": 2}, {"data"}, (1024, 50_000),
                       False),
}


@pytest.mark.parametrize("case", sorted(MESH_CASES))
def test_kernels_run_only_where_the_mesh_lets_them(case):
    """`pallas_xent.plan` reads the mesh once: no kernel where an axis is
    automatic, nor where the data-parallel step gathers the head
    (`train.sharded_update.shard_dim` shards it over the only manual axis
    of several devices); everywhere else the kernels read the head as
    stored."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    axes, manual, (h, v), want = MESH_CASES[case]
    seen = []

    def look(x):
        seen.append(pallas_xent.plan(8192, h, v, jnp.bfloat16,
                                     platform="tpu") is not None)
        return x

    x = jnp.zeros((8,))
    if axes is None:
        jax.make_jaxpr(look)(x)
    else:
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(
            tuple(axes.values())), tuple(axes))
        jax.make_jaxpr(shard_map(
            look, mesh=mesh, in_specs=P(), out_specs=P(),
            axis_names=manual or set(axes), check_vma=False))(x)
    assert seen == [want]
