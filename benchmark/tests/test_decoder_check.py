"""The teeth of the ``decoder_serve`` cell's check, at the small size on the
CPU: each fault is put into the PROGRAM (its weights, its configuration or
one of its functions), a few requests are served through the engine and the
batcher, and `decoder_serve_cell.judge_sample` — the function that decides
the cell's `correct` — must refuse them against the untouched reference.
The sound program must pass the same call."""
import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import decoder_serve_cell
import loadgen
import run

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "configs", "tiny-decoder.json")) as f:
    DOC = json.load(f)
with open(os.path.join(HERE, "data", "traffic", "decoder-serve.json")) as f:
    TRAFFIC = json.load(f)


def cell():
    return run.Cell(name="teeth", config=DOC, traffic=TRAFFIC, chips=1, seed=3,
                    seconds=1.0, trace=False, t0=0.0, workdir="/tmp",
                    rehearsal=True)


def serve_and_judge(program_params, cfg, true_params):
    from lstm_tensorspark_tpu.serve import SamplingParams, ServeServer
    from lstm_tensorspark_tpu.serve.engine import build_engine

    engine = build_engine(program_params, cfg, num_slots=8, num_pages=40,
                          page=8, max_context=96, prefill_buckets=(16, 32),
                          batch_buckets=(4,), max_prefill_rows=2,
                          interpret=True)
    rng = np.random.default_rng(9)
    outcomes, logits = [], {}
    with ServeServer(engine, max_active=4, window_ladder=(1, 4),
                     prefill_chunk=32) as server:
        for i, n in enumerate((37, 12, 21)):
            a = loadgen.Arrival(i, 0.0, n, 8, None, (3, i))
            o = loadgen.Outcome(a, due_at=0.0)
            o.prompt = tuple(int(t) for t in rng.integers(2, DOC["vocab_size"], n))
            req = server.generate(np.asarray(o.prompt), max_new_tokens=8,
                                  sampling=SamplingParams(greedy=True))
            o.tokens, o.ok = tuple(req.tokens), True
            logits[i] = np.asarray(req.token_logits)
            outcomes.append(o)
    return decoder_serve_cell.judge_sample(
        cell(), program_params, outcomes, [], logits, 3,
        reference_params=true_params)


@pytest.fixture(scope="module")
def sound():
    from lstm_tensorspark_tpu.models import decoder

    cfg = decoder.DecoderConfig.from_model(DOC)
    return decoder, cfg, decoder.init_decoder(7, cfg, dtype=jnp.float32)


def test_the_sound_program_passes(sound):
    _, cfg, params = sound
    judged = serve_and_judge(params, cfg, params)
    assert judged["ok"] and judged["logit_max"] < 1e-4, judged


def without_shared_expert(params):
    layers = [dict(layer, shared_down=jnp.zeros_like(layer["shared_down"]))
              if "shared_down" in layer else layer for layer in params["layers"]]
    return dict(params, layers=layers)


@pytest.mark.parametrize("fault", [
    "shared_expert_dropped", "factor_16_left_out", "weights_renormalised",
    "group_limit_ignored", "rope_on_the_nope_part", "c_kv_cached_before_its_norm",
    "m2_left_out_of_the_scale", "bf16_residual_stream", "bf16_router"])
def test_a_fault_fails_the_check(sound, monkeypatch, fault):
    decoder, cfg, params = sound
    from lstm_tensorspark_tpu.ops import moe

    program_params = params
    if fault == "shared_expert_dropped":
        program_params = without_shared_expert(params)
    elif fault == "factor_16_left_out":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif fault == "group_limit_ignored":
        cfg = dataclasses.replace(cfg, topk_group=cfg.n_group)
    elif fault == "m2_left_out_of_the_scale":
        cfg = dataclasses.replace(cfg, rope_mscale_all_dim=0.0)
    elif fault == "weights_renormalised":
        route = moe.route

        def renormalised(*a, scale, **k):
            experts, w = route(*a, scale=scale, **k)
            return experts, scale * w / w.sum(-1, keepdims=True)
        monkeypatch.setattr(moe, "route", renormalised)
    elif fault == "rope_on_the_nope_part":
        def rotate_all(q, nope, pos, inv_freq):
            d = 2 * inv_freq.shape[0]
            head = jnp.concatenate(
                [decoder.rope(q[..., :d], pos, inv_freq), q[..., d:nope]], -1)
            return head, decoder.rope(q[..., nope:], pos, inv_freq)
        monkeypatch.setattr(decoder, "split_query", rotate_all)
    elif fault == "c_kv_cached_before_its_norm":
        monkeypatch.setattr(
            decoder, "cached_latent",
            lambda kva, w, kv, pos, inv, eps: (
                kva[:, :kv], decoder.rope(kva[:, kv:], pos, inv)))
    elif fault == "bf16_residual_stream":
        mm = decoder._mm
        monkeypatch.setattr(decoder, "_mm", lambda x, w: mm(x, w).astype(
            jnp.bfloat16).astype(jnp.float32))
    elif fault == "bf16_router":
        route = moe.route
        monkeypatch.setattr(moe, "route", lambda x, w, **k: route(
            x.astype(jnp.bfloat16).astype(jnp.float32),
            w.astype(jnp.bfloat16).astype(jnp.float32), **k))
    judged = serve_and_judge(program_params, cfg, params)
    assert not judged["ok"], (fault, judged)
