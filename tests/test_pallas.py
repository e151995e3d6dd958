"""Fused Pallas LSTM kernel: interpret-mode parity on CPU (the kernel logic),
supported() gating, and the custom-VJP gradient path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_chip_compile import TRAIN_SHAPES

from lstm_tensorspark_tpu.ops import init_lstm_params, lstm_scan
from lstm_tensorspark_tpu.ops.pallas_bilstm import bilstm_supported
from lstm_tensorspark_tpu.ops.pallas_lstm import (
    _FUSEDX_MIN_T, _pad_to_lane, chosen_bwd_strategy, pallas_lstm_scan,
    supported,
)

B, T, D, H = 8, 10, 16, 128


def _setup():
    params = init_lstm_params(jax.random.PRNGKey(0), D, H)
    xs = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
    return params, xs


def test_supported_gating():
    assert not supported(B, H, platform="cpu")
    assert supported(8, 128, platform="tpu")
    assert not supported(7, 128, platform="tpu")  # sublane misalignment
    # lane misalignment is handled by internal padding now
    assert supported(8, 100, platform="tpu")
    assert supported(8, 650, platform="tpu")  # config 3, padded to 768


_PUBLISHED = {name: (b, t, d, h, masked)
              for name, b, t, d, h, masked, _ in TRAIN_SHAPES}


@pytest.mark.parametrize("shape,t,stacked,want", [
    ("ptb_char", None, False, "resident"),
    # both directions advance in the stacked kernel (residentx only); the
    # two-call fallback plans the same strategy
    ("imdb_bilstm_fwd", None, True, "residentx"),
    ("wikitext2", None, False, "resident"),         # H=650 padded to 768
    ("uci_seq2seq_enc", None, False, "resident"),
    ("wikitext103", None, False, "resident"),       # U^T alone is 8.4 MiB
    # one model, two plans: a long encoder takes the fused-x path, its
    # horizon-24 decoder the hoisted-xproj one
    ("uci_seq2seq_enc", 300, False, "residentx"),
    ("uci_seq2seq_enc", 24, False, "resident"),
], ids=["ptb_char", "imdb_bilstm", "wikitext2", "uci_seq2seq", "wikitext103",
        "long_seq2seq_encoder", "long_seq2seq_decoder"])
def test_backward_plan_at_published_shapes(shape, t, stacked, want):
    """The backward strategy the kernels' own decision functions choose
    at each BASELINE shape (bf16 weights, as the launch scripts run them):
    a cost-model change that flips a plan at a published shape fails
    here. Compiling the plans is tests/test_chip_compile.py's."""
    B, T, D, H, masked = _PUBLISHED[shape]
    T = t or T
    Dp = _pad_to_lane(D) if T >= _FUSEDX_MIN_T else None
    if stacked:
        assert bilstm_supported(B, H, D, T, platform="tpu",
                                param_dtype_bytes=2, has_mask=masked)
    assert chosen_bwd_strategy(B, T, _pad_to_lane(H), 2, has_mask=masked,
                               Dp=Dp) == want


def test_interpret_forward_parity():
    params, xs = _setup()
    (hT, cT), ys = pallas_lstm_scan(params, xs, interpret=True)
    (hT2, cT2), ys2 = lstm_scan(params, xs)
    np.testing.assert_allclose(ys, ys2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hT, hT2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cT, cT2, rtol=1e-5, atol=1e-5)


def test_interpret_with_carry():
    params, xs = _setup()
    h0 = jax.random.normal(jax.random.PRNGKey(2), (B, H))
    c0 = jax.random.normal(jax.random.PRNGKey(3), (B, H))
    (hT, _), ys = pallas_lstm_scan(params, xs, (h0, c0), interpret=True)
    (hT2, _), ys2 = lstm_scan(params, xs, (h0, c0))
    np.testing.assert_allclose(ys, ys2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hT, hT2, rtol=1e-5, atol=1e-5)


def test_grad_parity():
    """Custom VJP recomputes through the reference scan — grads must match."""
    params, xs = _setup()

    def loss_p(p):
        return jnp.mean(pallas_lstm_scan(p, xs, interpret=True)[1] ** 2)

    def loss_r(p):
        return jnp.mean(lstm_scan(p, xs)[1] ** 2)

    g1 = jax.grad(loss_p)(params)
    g2 = jax.grad(loss_r)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        g1, g2,
    )


def test_stacked_scan_fallback_on_cpu():
    """use_pallas on unsupported platform silently falls back to lax.scan."""
    from lstm_tensorspark_tpu.ops import stacked_lstm_scan

    params, xs = _setup()
    finals, ys = stacked_lstm_scan([params], xs, use_pallas=True)
    _, ys2 = lstm_scan(params, xs)
    np.testing.assert_allclose(ys, ys2, rtol=1e-6)


def test_supported_vmem_bound():
    """H=1024 f32 (resident U would be 16 MiB) now plans onto the TILED
    kernel instead of falling back; gigantic B·H still gates to False."""
    from lstm_tensorspark_tpu.ops.pallas_lstm import _plan_fwd

    assert supported(8, 1024, platform="tpu")  # tiled (config 5)
    assert _plan_fwd(8, 1024, 4, save_residuals=False)[0] == "tiled"
    assert _plan_fwd(8, 512, 4, save_residuals=False)[0] == "resident"
    assert supported(8, 512, platform="tpu")
    # a shape whose per-step blocks alone blow VMEM must still gate off
    assert not supported(4096, 4096, platform="tpu")


def test_grad_parity_with_remat_chunk():
    """remat_chunk threads through the custom VJP's recompute unchanged."""
    params, xs = _setup()

    def loss_p(p):
        return jnp.mean(
            pallas_lstm_scan(p, xs, remat_chunk=5, interpret=True)[1] ** 2
        )

    def loss_r(p):
        return jnp.mean(lstm_scan(p, xs)[1] ** 2)

    g1 = jax.grad(loss_p)(params)
    g2 = jax.grad(loss_r)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        g1, g2,
    )


def test_fused_backward_with_carry_cotangents():
    """Fused bwd must handle gradients flowing through (hT, cT) AND ys,
    with a nonzero initial carry."""
    params, xs = _setup()
    h0 = jax.random.normal(jax.random.PRNGKey(4), (B, H))
    c0 = jax.random.normal(jax.random.PRNGKey(5), (B, H))

    def loss(scan_fn):
        def f(p, h, c):
            (hT, cT), ys = scan_fn(p, xs, (h, c))
            return jnp.mean(ys**2) + jnp.sum(hT * 0.3) + jnp.sum(cT * 0.1)
        return f

    import functools
    g1 = jax.grad(loss(functools.partial(pallas_lstm_scan, interpret=True)),
                  argnums=(0, 1, 2))(params, h0, c0)
    g2 = jax.grad(loss(lstm_scan), argnums=(0, 1, 2))(params, h0, c0)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        g1, g2,
    )


def test_fused_backward_xs_gradient():
    """Gradients wrt the inputs (needed by stacked layers) match the scan."""
    params, _ = _setup()
    xs = jax.random.normal(jax.random.PRNGKey(6), (B, T, D))

    def lp(x):
        return jnp.mean(pallas_lstm_scan(params, x, interpret=True)[1] ** 2)

    def lr(x):
        return jnp.mean(lstm_scan(params, x)[1] ** 2)

    np.testing.assert_allclose(
        jax.grad(lp)(xs), jax.grad(lr)(xs), rtol=1e-4, atol=1e-6
    )


def test_fused_backward_bf16_close_to_f32():
    """bf16 compute dtype: fused bwd grads stay within bf16 tolerance of the
    f32 scan reference."""
    params, xs = _setup()

    def lp(p):
        return jnp.mean(
            pallas_lstm_scan(p, xs, compute_dtype=jnp.bfloat16,
                             interpret=True)[1] ** 2
        )

    def lr(p):
        return jnp.mean(lstm_scan(p, xs)[1] ** 2)

    g1 = jax.grad(lp)(params)
    g2 = jax.grad(lr)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.1, atol=0.02,
        ),
        g1, g2,
    )


def test_tiled_forward_and_grad_parity_h1024():
    """H=1024 f32 selects the TILED kernels (U streamed in row-tiles, dU
    computed outside); forward and grads must match the scan reference."""
    from lstm_tensorspark_tpu.ops.pallas_lstm import _plan_bwd, _plan_fwd

    assert _plan_fwd(8, 1024, 4, save_residuals=True)[0] == "tiled"
    assert _plan_bwd(8, 1024, 4)[0] == "tiled"
    params = init_lstm_params(jax.random.PRNGKey(7), 32, 1024)
    xs = jax.random.normal(jax.random.PRNGKey(8), (8, 4, 32))
    (hT, cT), ys = pallas_lstm_scan(params, xs, interpret=True)
    (hT2, cT2), ys2 = lstm_scan(params, xs)
    np.testing.assert_allclose(ys, ys2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hT, hT2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cT, cT2, rtol=1e-5, atol=1e-5)

    def lp(p, x):
        return jnp.mean(pallas_lstm_scan(p, x, interpret=True)[1] ** 2)

    def lr(p, x):
        return jnp.mean(lstm_scan(p, x)[1] ** 2)

    g1 = jax.grad(lp, argnums=(0, 1))(params, xs)
    g2 = jax.grad(lr, argnums=(0, 1))(params, xs)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        g1, g2,
    )


def test_padded_h650_parity():
    """H=650 (config 3) pads to 768 internally; forward AND grads must be
    exact vs the unpadded scan (padding analysis: dz_pad = 0 identically)."""
    params = init_lstm_params(jax.random.PRNGKey(9), 48, 650)
    xs = jax.random.normal(jax.random.PRNGKey(10), (8, 6, 48))
    h0 = jax.random.normal(jax.random.PRNGKey(11), (8, 650))
    c0 = jax.random.normal(jax.random.PRNGKey(12), (8, 650))
    (hT, cT), ys = pallas_lstm_scan(params, xs, (h0, c0), interpret=True)
    (hT2, cT2), ys2 = lstm_scan(params, xs, (h0, c0))
    assert ys.shape == ys2.shape == (8, 6, 650)
    np.testing.assert_allclose(ys, ys2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hT, hT2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cT, cT2, rtol=1e-5, atol=1e-5)

    def lp(p, h, c):
        (hT, cT), ys = pallas_lstm_scan(p, xs, (h, c), interpret=True)
        return jnp.mean(ys**2) + jnp.sum(hT * 0.3) + jnp.sum(cT * 0.1)

    def lr(p, h, c):
        (hT, cT), ys = lstm_scan(p, xs, (h, c))
        return jnp.mean(ys**2) + jnp.sum(hT * 0.3) + jnp.sum(cT * 0.1)

    g1 = jax.grad(lp, argnums=(0, 1, 2))(params, h0, c0)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(params, h0, c0)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        g1, g2,
    )


def test_residual_hbm_heuristic(monkeypatch):
    """Residual bytes above the HBM budget select the recompute backward
    (no z residuals saved) — ADVICE.md's memory-regression guard."""
    import lstm_tensorspark_tpu.ops.pallas_lstm as pallas_mod

    params, xs = _setup()
    g_fused = jax.grad(
        lambda p: jnp.mean(pallas_lstm_scan(p, xs, interpret=True)[1] ** 2)
    )(params)
    monkeypatch.setattr(pallas_mod, "_RESIDUAL_HBM_BUDGET", 1)  # force off
    g_recompute = jax.grad(
        lambda p: jnp.mean(pallas_lstm_scan(p, xs, interpret=True)[1] ** 2)
    )(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        g_fused, g_recompute,
    )


# ---------------------------------------------------------------------------
# masked / reversed scans (round 3: configs 2 & 4 fused-path coverage)
# ---------------------------------------------------------------------------


def _lengths_mask(key, b, t):
    lengths = jax.random.randint(key, (b,), 1, t + 1)
    return jnp.arange(t)[None, :] < lengths[:, None]


def test_masked_forward_parity():
    params, xs = _setup()
    mask = _lengths_mask(jax.random.PRNGKey(20), B, T)
    (hT, cT), ys = pallas_lstm_scan(params, xs, mask=mask, interpret=True)
    (hT2, cT2), ys2 = lstm_scan(params, xs, mask=mask)
    np.testing.assert_allclose(ys, ys2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hT, hT2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cT, cT2, rtol=1e-5, atol=1e-5)


def test_reverse_forward_parity():
    params, xs = _setup()
    h0 = jax.random.normal(jax.random.PRNGKey(21), (B, H))
    c0 = jax.random.normal(jax.random.PRNGKey(22), (B, H))
    (hT, cT), ys = pallas_lstm_scan(
        params, xs, (h0, c0), reverse=True, interpret=True
    )
    (hT2, cT2), ys2 = lstm_scan(params, xs, (h0, c0), reverse=True)
    np.testing.assert_allclose(ys, ys2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hT, hT2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cT, cT2, rtol=1e-5, atol=1e-5)


def test_masked_reverse_parity():
    """The bi-LSTM's backward direction: reversed scan over a right-padded
    batch with a carry-freeze mask. Forward AND grads must match."""
    params, xs = _setup()
    mask = _lengths_mask(jax.random.PRNGKey(23), B, T)

    def lp(p, x):
        (hT, cT), ys = pallas_lstm_scan(
            p, x, mask=mask, reverse=True, interpret=True
        )
        return jnp.mean(ys**2) + jnp.sum(hT * 0.3) + jnp.sum(cT * 0.1)

    def lr(p, x):
        (hT, cT), ys = lstm_scan(p, x, mask=mask, reverse=True)
        return jnp.mean(ys**2) + jnp.sum(hT * 0.3) + jnp.sum(cT * 0.1)

    np.testing.assert_allclose(lp(params, xs), lr(params, xs),
                               rtol=1e-5, atol=1e-6)
    g1 = jax.grad(lp, argnums=(0, 1))(params, xs)
    g2 = jax.grad(lr, argnums=(0, 1))(params, xs)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        g1, g2,
    )


def test_masked_grad_parity_fused_bwd():
    """Masked FUSED backward (not the recompute fallback): the masked
    cotangent algebra inside _lstm_bwd_kernel must match lstm_scan grads."""
    from lstm_tensorspark_tpu.ops.pallas_lstm import _plan_bwd

    assert _plan_bwd(B, H, 4, True) is not None  # fused bwd is the live path
    params, xs = _setup()
    mask = _lengths_mask(jax.random.PRNGKey(24), B, T)
    h0 = jax.random.normal(jax.random.PRNGKey(25), (B, H))
    c0 = jax.random.normal(jax.random.PRNGKey(26), (B, H))

    def lp(p, x, h, c):
        (hT, cT), ys = pallas_lstm_scan(p, x, (h, c), mask=mask,
                                        interpret=True)
        return jnp.mean(ys**2) + jnp.sum(hT * 0.3) + jnp.sum(cT * 0.1)

    def lr(p, x, h, c):
        (hT, cT), ys = lstm_scan(p, x, (h, c), mask=mask)
        return jnp.mean(ys**2) + jnp.sum(hT * 0.3) + jnp.sum(cT * 0.1)

    g1 = jax.grad(lp, argnums=(0, 1, 2, 3))(params, xs, h0, c0)
    g2 = jax.grad(lr, argnums=(0, 1, 2, 3))(params, xs, h0, c0)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        g1, g2,
    )


def test_masked_tiled_parity():
    """Masked TILED kernels (H=1024 → U streamed): forward + grads."""
    from lstm_tensorspark_tpu.ops.pallas_lstm import _plan_bwd, _plan_fwd

    assert _plan_fwd(8, 1024, 4, save_residuals=True, has_mask=True)[0] == "tiled"
    assert _plan_bwd(8, 1024, 4, True)[0] == "tiled"
    params = init_lstm_params(jax.random.PRNGKey(27), 32, 1024)
    xs = jax.random.normal(jax.random.PRNGKey(28), (8, 4, 32))
    mask = _lengths_mask(jax.random.PRNGKey(29), 8, 4)

    (hT, cT), ys = pallas_lstm_scan(params, xs, mask=mask, interpret=True)
    (hT2, cT2), ys2 = lstm_scan(params, xs, mask=mask)
    np.testing.assert_allclose(ys, ys2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hT, hT2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cT, cT2, rtol=1e-5, atol=1e-5)

    def lp(p, x):
        return jnp.mean(pallas_lstm_scan(p, x, mask=mask, interpret=True)[1] ** 2)

    def lr(p, x):
        return jnp.mean(lstm_scan(p, x, mask=mask)[1] ** 2)

    g1 = jax.grad(lp, argnums=(0, 1))(params, xs)
    g2 = jax.grad(lr, argnums=(0, 1))(params, xs)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        g1, g2,
    )


def test_masked_recompute_bwd_parity(monkeypatch):
    """Masked scan with the recompute backward (residual budget forced to 0):
    the fallback must thread the mask through lstm_scan."""
    import lstm_tensorspark_tpu.ops.pallas_lstm as pallas_mod

    monkeypatch.setattr(pallas_mod, "_RESIDUAL_HBM_BUDGET", 1)
    params, xs = _setup()
    mask = _lengths_mask(jax.random.PRNGKey(30), B, T)

    def lp(p):
        return jnp.mean(
            pallas_lstm_scan(p, xs, mask=mask, interpret=True)[1] ** 2
        )

    def lr(p):
        return jnp.mean(lstm_scan(p, xs, mask=mask)[1] ** 2)

    g1 = jax.grad(lp)(params)
    g2 = jax.grad(lr)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        g1, g2,
    )


def test_masked_padded_h650_parity():
    """Mask + lane padding together (config-3-like H=650 → padded 768)."""
    params = init_lstm_params(jax.random.PRNGKey(31), 48, 650)
    xs = jax.random.normal(jax.random.PRNGKey(32), (8, 6, 48))
    mask = _lengths_mask(jax.random.PRNGKey(33), 8, 6)
    (hT, cT), ys = pallas_lstm_scan(params, xs, mask=mask, interpret=True)
    (hT2, cT2), ys2 = lstm_scan(params, xs, mask=mask)
    np.testing.assert_allclose(ys, ys2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hT, hT2, rtol=1e-5, atol=1e-5)

    def lp(p):
        return jnp.mean(pallas_lstm_scan(p, xs, mask=mask, interpret=True)[1] ** 2)

    def lr(p):
        return jnp.mean(lstm_scan(p, xs, mask=mask)[1] ** 2)

    g1 = jax.grad(lp)(params)
    g2 = jax.grad(lr)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        g1, g2,
    )


# ---------------------------------------------------------------------------
# fully-fused residentx strategy (in-kernel xproj + recompute-z backward)
# ---------------------------------------------------------------------------


def test_residentx_is_planned_for_small_shapes():
    from lstm_tensorspark_tpu.ops.pallas_lstm import _plan_bwd, _plan_fwd

    # config-1/2/4 shape class: both directions of the pair fit
    assert _plan_fwd(64, 128, 2, save_residuals=True, Dp=128)[0] == "residentx"
    assert _plan_bwd(64, 128, 2, False, 128)[0] == "residentx"
    assert _plan_fwd(64, 256, 2, save_residuals=True, Dp=512)[0] == "residentx"
    assert _plan_bwd(64, 256, 2, False, 512)[0] == "residentx"
    # H=1024: U+U^T resident cannot fit — falls to the legacy strategies
    assert _plan_bwd(8, 1024, 4, False, 128)[0] == "tiled"
    # no Dp (hoisted-xproj callers): residentx is never offered
    assert _plan_fwd(64, 128, 2, save_residuals=True)[0] == "resident"


def test_residentx_grads_with_mask_carry_and_padded_d(monkeypatch):
    """The fully-fused pair at an off-lane input width (D=50 → padded 128):
    forward + grads (params, xs, carry) must match lstm_scan, mask on.
    (_FUSEDX_MIN_T forced to 0 so the short test sequence takes the path.)"""
    import lstm_tensorspark_tpu.ops.pallas_lstm as pallas_mod
    from lstm_tensorspark_tpu.ops.pallas_lstm import _plan_bwd

    monkeypatch.setattr(pallas_mod, "_FUSEDX_MIN_T", 0)
    D_odd = 50
    assert _plan_bwd(B, H, 4, True, 128)[0] == "residentx"
    params = init_lstm_params(jax.random.PRNGKey(40), D_odd, H)
    xs = jax.random.normal(jax.random.PRNGKey(41), (B, T, D_odd))
    mask = _lengths_mask(jax.random.PRNGKey(42), B, T)
    h0 = jax.random.normal(jax.random.PRNGKey(43), (B, H))
    c0 = jax.random.normal(jax.random.PRNGKey(44), (B, H))

    def lp(p, x, h, c):
        (hT, cT), ys = pallas_lstm_scan(p, x, (h, c), mask=mask,
                                        interpret=True)
        return jnp.mean(ys**2) + jnp.sum(hT * 0.3) + jnp.sum(cT * 0.1)

    def lr(p, x, h, c):
        (hT, cT), ys = lstm_scan(p, x, (h, c), mask=mask)
        return jnp.mean(ys**2) + jnp.sum(hT * 0.3) + jnp.sum(cT * 0.1)

    np.testing.assert_allclose(lp(params, xs, h0, c0), lr(params, xs, h0, c0),
                               rtol=1e-5, atol=1e-6)
    g1 = jax.grad(lp, argnums=(0, 1, 2, 3))(params, xs, h0, c0)
    g2 = jax.grad(lr, argnums=(0, 1, 2, 3))(params, xs, h0, c0)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        g1, g2,
    )


def test_legacy_resident_path_still_works(monkeypatch):
    """Force the hoisted-xproj resident pair (residentx priced out) — the
    legacy path must stay healthy for shapes where W cannot be resident."""
    import lstm_tensorspark_tpu.ops.pallas_lstm as pallas_mod

    monkeypatch.setattr(pallas_mod, "_residentx_fwd_vmem",
                        lambda *a, **k: 10**12)
    monkeypatch.setattr(pallas_mod, "_residentx_bwd_vmem",
                        lambda *a, **k: 10**12)
    assert pallas_mod._plan_fwd(B, H, 4, save_residuals=True,
                                Dp=128)[0] == "resident"
    params, xs = _setup()
    mask = _lengths_mask(jax.random.PRNGKey(45), B, T)

    def lp(p):
        return jnp.mean(
            pallas_lstm_scan(p, xs, mask=mask, interpret=True)[1] ** 2
        )

    def lr(p):
        return jnp.mean(lstm_scan(p, xs, mask=mask)[1] ** 2)

    g1 = jax.grad(lp)(params)
    g2 = jax.grad(lr)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        g1, g2,
    )


def test_du_hoist_loosens_resident_bwd_plan():
    """dU is contracted outside every sequential kernel (from the streamed
    dz), so the backward cost model carries no [H,4H] f32 accumulator and
    no h_prev input stream. The config-4 encoder class (B=64, H=256 bf16,
    no mask, hoisted xproj) fits the RESIDENT backward again — under the
    old accounting it priced out to tiled. Big-H shapes still tile."""
    from lstm_tensorspark_tpu.ops.pallas_lstm import _plan_bwd

    assert _plan_bwd(64, 256, 2, False, None)[0] == "resident"
    # r4 chunk-flexible planning + bf16 streams: big-H bf16 shapes now fit
    # the U-resident backward at a SMALLER time chunk instead of paying
    # tiled's per-timestep U^T re-stream
    assert _plan_bwd(64, 768, 2, False, None) == ("resident", 2)
    assert _plan_bwd(32, 1024, 2, False, None) == ("resident", 2)
    # f32 streams keep big-H on the tiled strategy (U alone ~16.8 MB f32
    # at H=1024 exceeds the VMEM budget)
    assert _plan_bwd(64, 768, 4, False, None)[0] == "tiled"
    assert _plan_bwd(32, 1024, 4, False, None)[0] == "tiled"


def test_bf16_stream_residuals_grad_tolerance(monkeypatch):
    """r4 bandwidth fix: under bf16 compute the z/dz/xproj HBM streams
    are STORED bf16 (gate math stays f32 in-kernel). Gradients through
    the fused backward must stay within bf16-scale tolerance of the f32
    reference, and LSTM_TSP_RESIDUAL_F32=1 must restore the old f32
    streams exactly."""
    import functools

    import lstm_tensorspark_tpu.ops.pallas_lstm as pallas_mod

    params, xs = _setup()

    def loss(run):
        def f(p, x):
            (hT, cT), ys = run(p, x)
            return jnp.mean(ys ** 2) + jnp.mean(hT) + jnp.mean(cT ** 2)
        return f

    run_p = functools.partial(pallas_lstm_scan, compute_dtype=jnp.bfloat16,
                              interpret=True)
    run_r = functools.partial(lstm_scan, compute_dtype=jnp.bfloat16)
    g_bf16 = jax.grad(loss(run_p), argnums=(0, 1))(params, xs)
    g_ref = jax.grad(loss(run_r), argnums=(0, 1))(params, xs)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-2, atol=5e-3),
        g_bf16, g_ref,
    )

    # kill-switch: f32 streams under bf16 compute (the A/B lever)
    monkeypatch.setenv("LSTM_TSP_RESIDUAL_F32", "1")
    assert pallas_mod._rbytes(2) == 4
    g_f32s = jax.grad(loss(run_p), argnums=(0, 1))(params, xs)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-2, atol=5e-3),
        g_f32s, g_ref,
    )


def test_bf16_tiled_bigh_grad_parity():
    """ADVICE r4: the bf16 stored-z rounding (forward computes gates from
    f32 z, backward recomputes them from the bf16-rounded STORED z) must
    stay within bf16 tolerance on the TILED path too, not just
    resident/residentx — H=1536 bf16 is the smallest shape that spills
    past every resident chunk and plans tiled for both passes."""
    from lstm_tensorspark_tpu.ops.pallas_lstm import (
        _plan_bwd, _plan_fwd, chosen_bwd_strategy,
    )

    Bt, Tt, Dt, Ht = 8, 4, 16, 1536
    assert _plan_fwd(Bt, Ht, 2, save_residuals=True)[0] == "tiled"
    assert _plan_bwd(Bt, Ht, 2, False, None)[0] == "tiled"
    assert chosen_bwd_strategy(Bt, Tt, Ht, 2) == "tiled"

    params = init_lstm_params(jax.random.PRNGKey(11), Dt, Ht)
    xs = jax.random.normal(jax.random.PRNGKey(12), (Bt, Tt, Dt))

    def lp(p):
        return jnp.mean(pallas_lstm_scan(
            p, xs, compute_dtype=jnp.bfloat16, interpret=True)[1] ** 2)

    def lr(p):
        return jnp.mean(lstm_scan(p, xs, compute_dtype=jnp.bfloat16)[1] ** 2)

    np.testing.assert_allclose(
        jax.jit(lp)(params), jax.jit(lr)(params), rtol=2e-2, atol=2e-3)
    g1 = jax.grad(lp)(params)
    g2 = jax.grad(lr)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=8e-2, atol=8e-3),
        g1, g2,
    )


def test_f32_compute_keeps_f32_streams():
    """f32 compute must keep bit-exact f32 residual streams — the exact
    interpret-mode parities above depend on it."""
    from lstm_tensorspark_tpu.ops.pallas_lstm import (
        _rbytes, _residual_dtype,
    )

    assert _residual_dtype(jnp.float32) == jnp.float32
    assert _rbytes(4) == 4
    assert _residual_dtype(jnp.bfloat16) == jnp.bfloat16
    assert _rbytes(2) == 2


def test_chunk2_resident_bf16_bigh_parity():
    """The r4 plan flip: H=650-class bf16 shapes run the U-RESIDENT pair
    at time chunk 2 (instead of tiled's per-timestep U re-stream). Pin
    the plan and check fwd+grad parity through the chunk-2 kernels in
    interpret mode at bf16 tolerance."""
    from lstm_tensorspark_tpu.ops.pallas_lstm import _plan_bwd, _plan_fwd

    Bc, Tc, Dc, Hc = 64, 6, 16, 650  # padded H = 768
    assert _plan_fwd(Bc, 768, 2, save_residuals=True) == ("resident", 2)
    assert _plan_bwd(Bc, 768, 2, False, None) == ("resident", 2)

    params = init_lstm_params(jax.random.PRNGKey(7), Dc, Hc)
    xs = jax.random.normal(jax.random.PRNGKey(8), (Bc, Tc, Dc))
    (hT, cT), ys = pallas_lstm_scan(params, xs, compute_dtype=jnp.bfloat16,
                                    interpret=True)
    (hT2, cT2), ys2 = lstm_scan(params, xs, compute_dtype=jnp.bfloat16)
    np.testing.assert_allclose(ys, ys2, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(hT, hT2, rtol=2e-2, atol=2e-2)

    def lp(p):
        return jnp.mean(pallas_lstm_scan(
            p, xs, compute_dtype=jnp.bfloat16, interpret=True)[1] ** 2)

    def lr(p):
        return jnp.mean(lstm_scan(p, xs, compute_dtype=jnp.bfloat16)[1] ** 2)

    g1 = jax.grad(lp)(params)
    g2 = jax.grad(lr)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=8e-2, atol=8e-3),
        g1, g2,
    )


@pytest.mark.parametrize("strategy", ["residentx", "resident", "tiled"])
def test_carry_grads_with_unequal_gate_blocks_of_u(monkeypatch, strategy):
    """The backward kernels read U as stored, [H, 4H], and contract dz's
    gate axis with U's second axis. With the four gate blocks of U at
    scales 0.25 / 1 / 2 / 4 a contraction over the wrong axis of one block,
    or a column tile taken from the wrong place, moves dh0 and dxs far
    outside the tolerance; the tiled case runs one tile per gate block."""
    import lstm_tensorspark_tpu.ops.pallas_lstm as pallas_mod

    if strategy == "residentx":
        monkeypatch.setattr(pallas_mod, "_FUSEDX_MIN_T", 0)
    if strategy == "tiled":
        monkeypatch.setattr(pallas_mod, "_resident_bwd_vmem",
                            lambda *a, **k: 10**12)
        monkeypatch.setattr(
            pallas_mod, "_tiled_bwd_vmem",
            lambda B, H, pbytes, ttile, has_mask=False:
                0 if ttile == H else 10**12)
    Dp = _pad_to_lane(D) if strategy == "residentx" else None
    assert chosen_bwd_strategy(B, T, H, 4, Dp=Dp) == strategy

    params, xs = _setup()
    params = params._replace(
        U_i=params.U_i * 0.25, U_g=params.U_g * 2.0, U_o=params.U_o * 4.0)
    h0 = jax.random.normal(jax.random.PRNGKey(4), (B, H))
    c0 = jax.random.normal(jax.random.PRNGKey(5), (B, H))

    def loss(scan_fn):
        def f(h, x):
            (hT, cT), ys = scan_fn(params, x, (h, c0))
            return jnp.mean(ys**2) + jnp.sum(hT * 0.3) + jnp.sum(cT * 0.1)
        return f

    import functools
    g1 = jax.grad(loss(functools.partial(pallas_lstm_scan, interpret=True)),
                  argnums=(0, 1))(h0, xs)
    g2 = jax.grad(loss(lstm_scan), argnums=(0, 1))(h0, xs)
    for got, want in zip(g1, g2):
        assert float(jnp.abs(want).max()) > 1e-3  # the carry path is live
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
