"""REAL-TPU parallel-scan BPTT gate (ops/parallel_scan.py): compile the
associative-scan backward on the actual chip, assert gradient parity
against the sequential VJP, and measure warm train-step throughput
assoc vs sequential on the T=400 bucket.

This closes the CPU blind spot the same way
tests_tpu/test_pallas_decode_tpu.py does for the serve plane: the CPU
suite proves the ALGEBRA (tests/test_parallel_scan.py — grads allclose
at fp64-validated tolerances), but the perf claim is about the
accelerator's latency-bound sequential chain. On CPU the assoc path's
extra dense-compose FLOPs usually lose; on TPU the log-depth tree of MXU matmuls
must be at least break-even at T=400 or the plan/tile is mis-chosen.

Perf gate: assoc tokens/s >= 1.0x sequential (median of warm repeats,
same jitted step, same data). The measured ratio prints either way —
the trajectory datapoint for the training-perf trendline.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lstm_tensorspark_tpu.models import LMConfig, init_lm
from lstm_tensorspark_tpu.models.lstm_lm import lm_loss
from lstm_tensorspark_tpu.ops import parallel_scan

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="requires a real TPU"
)

# the T=400 bucket (the IMDB sequence length — ROADMAP open item 2(b));
# H sized so the dense chunk-operator plan fits the default budget
B, T, V, H, L = 16, 400, 1024, 128, 1


def _step_fn(bptt):
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=L,
                   compute_dtype="bfloat16", bptt=bptt)

    @jax.jit
    def step(params, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: lm_loss(p, batch, cfg), has_aux=True)(params)
        return loss, grads

    return cfg, step


def _batch(rng):
    toks = rng.randint(0, V, size=(B, T + 1)).astype(np.int32)
    return {"inputs": jnp.asarray(toks[:, :-1]),
            "targets": jnp.asarray(toks[:, 1:])}


def test_assoc_backward_compiles_and_matches_on_tpu():
    assert parallel_scan.plan_fits(B, T, H), (
        "gate config must fit the assoc plan — shrink H/B or raise "
        "LSTM_TSP_ASSOC_BUDGET_MB")
    rng = np.random.RandomState(0)
    batch = _batch(rng)
    cfg, step = _step_fn("assoc")
    params = init_lm(jax.random.PRNGKey(3), cfg)
    loss_a, grads_a = step(params, batch)
    _, step_s = _step_fn("sequential")
    loss_s, grads_s = step_s(params, batch)
    np.testing.assert_allclose(float(loss_a), float(loss_s),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(grads_a), jax.tree.leaves(grads_s)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=3e-2, atol=3e-3)


def test_train_step_perf_gate_t400():
    """Warm train-step throughput at T=400, assoc vs sequential — the
    parallel-scan backward must not be SLOWER than the chain it replaces
    (>= 1.0x tokens/s; the measured ratio prints as the trajectory
    datapoint either way)."""
    rng = np.random.RandomState(1)
    batch = _batch(rng)
    results = {}
    for mode in ("sequential", "assoc"):
        cfg, step = _step_fn(mode)
        params = init_lm(jax.random.PRNGKey(3), cfg)
        loss, grads = step(params, batch)   # compile + warm
        jax.block_until_ready(loss)
        times = []
        for _ in range(20):
            t0 = time.perf_counter()
            loss, grads = step(params, batch)
            jax.block_until_ready(loss)
            times.append(time.perf_counter() - t0)
        times.sort()
        med = times[len(times) // 2]
        results[mode] = B * T / med
    ratio = results["assoc"] / results["sequential"]
    print(f"\nassoc bptt T={T} B={B} H={H}: {results['assoc']:,.0f} tok/s "
          f"vs sequential {results['sequential']:,.0f} ({ratio:.2f}x)")
    assert ratio >= 1.0, (
        f"assoc backward SLOWER than sequential ({ratio:.2f}x) — re-plan "
        "the tile (pick_tile) or pin --bptt-mode sequential and investigate")
