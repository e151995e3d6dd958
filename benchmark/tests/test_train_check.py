"""The train cells' `correct` must have teeth: a program that leaves part of
the mathematics out has to fail it. Tiny, against the program itself; and at
the published widths, where the reference stands in for the program (the CPU
has no kernels) and the faults are made in its weights."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import train_cell


def no_recurrence(params):
    return dict(params, layers=[
        l._replace(**{f"U_{g}": jnp.zeros_like(getattr(l, f"U_{g}")) for g in "ifgo"})
        for l in params["layers"]])


def top_layer_dropped(params):
    return dict(params, layers=params["layers"][:-1])


def gates_swapped(params):
    return dict(params, layers=[
        l._replace(W_i=l.W_f, W_f=l.W_i, U_i=l.U_f, U_f=l.U_i, b_i=l.b_f, b_f=l.b_i)
        for l in params["layers"]])


FAULTS = [no_recurrence, top_layer_dropped, gates_swapped]


def batch_of(vocab, rows, steps):
    tokens = np.random.default_rng(7).integers(2, vocab, size=(rows, steps + 1))
    tokens = tokens.astype(np.int32)
    return jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])


@pytest.fixture(scope="module")
def tiny():
    from lstm_tensorspark_tpu.models import LMConfig

    cfg = LMConfig(vocab_size=97, hidden_size=24, num_layers=3)
    params = train_cell.check_params(3, cfg)
    inputs, targets = batch_of(97, 6, 20)
    return cfg, params, inputs, targets, train_cell.reference_outputs(
        params, inputs, targets)


def test_the_program_passes(tiny):
    cfg, params, inputs, targets, want = tiny
    report = train_cell.compare(
        train_cell.program_outputs(params, cfg, inputs, targets), want)
    assert report["ok"], report
    assert report["hidden_rel_max"] < 1e-4 and report["grad_rel_l2"] < 1e-4


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_a_program_that_leaves_mathematics_out_fails(tiny, fault):
    cfg, params, inputs, targets, want = tiny
    faulty = fault(params)
    got = train_cell.program_outputs(
        faulty, dataclasses.replace(cfg, num_layers=len(faulty["layers"])),
        inputs, targets)
    if fault is top_layer_dropped:     # its gradient tree is a layer short
        got["grads"] = want["grads"]
    report = train_cell.compare(got, want)
    assert not report["ok"], report
    assert report["hidden_rel_l2"] > train_cell.TOLERANCE["hidden_rel_l2"]


def test_a_wrong_backward_alone_fails(tiny):
    """Forward right, one layer's recurrent gradient wrong: only the
    gradient comparison can see it."""
    cfg, params, inputs, targets, want = tiny
    got = train_cell.program_outputs(params, cfg, inputs, targets)
    layers = list(got["grads"]["layers"])
    layers[0] = layers[0]._replace(U_g=0.5 * layers[0].U_g)
    got["grads"] = dict(got["grads"], layers=layers)
    report = train_cell.compare(got, want)
    assert not report["ok"] and report["grad_worst_leaf"].endswith("U_g")
    assert report["hidden_rel_l2"] < 1e-4


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_faults_show_at_the_published_widths(fault, published):
    params, inputs, targets, want = published
    got = train_cell.reference_outputs(fault(params), inputs, targets)
    if fault is top_layer_dropped:
        got["grads"] = want["grads"]
    report = train_cell.compare(got, want)
    assert not report["ok"], report
    # by their own size, not by a rounding's
    assert report["hidden_rel_l2"] > 0.3, report
    if fault is not top_layer_dropped:
        assert report["grad_rel_l2"] > 0.3, report


@pytest.fixture(scope="module")
def published():
    from lstm_tensorspark_tpu.models import LMConfig

    cfg = LMConfig(vocab_size=50000, hidden_size=1024, num_layers=4)
    params = train_cell.check_params(11, cfg)
    inputs, targets = batch_of(50000, 2, 24)
    return params, inputs, targets, train_cell.reference_outputs(
        params, inputs, targets, slices=1)
