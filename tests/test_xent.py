"""The two head + loss functions of ops/xent.py against the plain
logsumexp loss and `jax.grad` of it — values AND gradients: the
vocab-chunked one (padded-V and tied-head cases) and the dense one with
its hand-written backward (float32 and bf16 logits, tied head, row counts
off the tile grid, the word-LM vocabularies)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
