"""The plain reference against the program's own loss and greedy decoding,
tiny, on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference import lstm_lm as reference


@pytest.fixture(scope="module")
def model():
    from lstm_tensorspark_tpu.models import LMConfig, init_lm

    cfg = LMConfig(vocab_size=97, hidden_size=24, num_layers=3)
    return cfg, init_lm(jax.random.PRNGKey(3), cfg)


def test_loss_agrees_with_the_programs(model):
    from lstm_tensorspark_tpu.models import lm_loss

    cfg, params = model
    tokens = np.random.default_rng(0).integers(2, 97, size=(5, 12)).astype(np.int32)
    batch = {"inputs": jnp.asarray(tokens[:, :-1]), "targets": jnp.asarray(tokens[:, 1:])}
    want, top = reference.loss(params, batch["inputs"], batch["targets"])
    got, _ = lm_loss(params, batch, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(top) > 0


def test_judge_accepts_the_programs_greedy_tokens_and_refuses_wrong_ones(model):
    from lstm_tensorspark_tpu.models import make_generate_fn

    cfg, params = model
    prompt = np.asarray([5, 9, 33, 2, 71], np.int32)
    gen = make_generate_fn(cfg, max_new_tokens=7, greedy=True)
    out = np.asarray(gen(params, prompt[None, :], jax.random.PRNGKey(0)))[0]
    produced = out[prompt.size:]
    ok, exact, ties, worst = reference.judge_greedy(
        params, prompt, produced, rel_tol=2.0 ** -16)
    assert ok and exact + ties == 7
    wrong = produced.copy()
    wrong[3] = (wrong[3] + 1) % 97
    ok, *_ = reference.judge_greedy(params, prompt, wrong, rel_tol=2.0 ** -16)
    assert not ok


def test_judge_starts_from_the_carries_it_is_given(model):
    """A next turn of a resident session is judged from that session's
    carries: the reference's own greedy tokens from them pass, and the same
    tokens judged from zero carries do not."""
    cfg, params = model
    rng = np.random.default_rng(1)
    carries = [(jnp.asarray(rng.uniform(-0.9, 0.9, 24), jnp.float32),
                jnp.asarray(rng.uniform(-0.9, 0.9, 24), jnp.float32))
               for _ in range(cfg.num_layers)]
    stream = [5, 9, 33]
    for _ in range(6):
        z = reference.logits(params, jnp.asarray([stream], jnp.int32),
                             [(h[None], c[None]) for h, c in carries])
        stream.append(int(jnp.argmax(z[0, -1])))
    ok, exact, *_ = reference.judge_greedy(
        params, stream[:3], stream[3:], rel_tol=2.0 ** -16, carries=carries)
    assert ok and exact == 6
    ok, *_ = reference.judge_greedy(params, stream[:3], stream[3:], rel_tol=2.0 ** -16)
    assert not ok
