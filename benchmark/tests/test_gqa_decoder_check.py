"""The teeth of the ``gqa_decoder_serve`` cell's check, at the small size on
the CPU: each fault is put into the PROGRAM (its configuration, one of its
functions or its cache), a few requests several windows long are served
through the engine and the batcher, and
`gqa_decoder_serve_cell.judge_sample` (the function that decides the cell's
`correct`) must refuse them against the untouched reference. The sound
program must pass the same call."""
import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import gqa_decoder_serve_cell
import loadgen
import run

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "configs", "tiny-mellum.json")) as f:
    DOC = json.load(f)
with open(os.path.join(HERE, "data", "traffic", "gqa-decoder-serve.json")) as f:
    TRAFFIC = json.load(f)


def cell():
    return run.Cell(name="teeth", config=DOC, traffic=TRAFFIC, chips=1, seed=3,
                    seconds=1.0, trace=False, t0=0.0, workdir="/tmp",
                    rehearsal=True)


def serve_and_judge(params, cfg, cache_cfg=None):
    from lstm_tensorspark_tpu.models import decoder
    from lstm_tensorspark_tpu.serve import SamplingParams, ServeServer
    from lstm_tensorspark_tpu.serve.engine import build_engine

    engine = build_engine(params, cfg, num_slots=8, num_pages=(60, 30),
                          page=4, max_context=96, prefill_buckets=(16, 32),
                          batch_buckets=(4,), max_prefill_rows=2,
                          interpret=True)
    if cache_cfg is not None:       # the cache keeps another window's pages
        engine.cache.kinds = decoder.cache_kinds(cache_cfg, (60, 30))
    rng = np.random.default_rng(9)
    outcomes, logits = [], {}
    with ServeServer(engine, max_active=4, window_ladder=(1, 4),
                     prefill_chunk=32) as server:
        for i, n in enumerate((37, 12, 45)):
            a = loadgen.Arrival(i, 0.0, n, 8, None, (3, i))
            o = loadgen.Outcome(a, due_at=0.0)
            o.prompt = tuple(int(t) for t in rng.integers(2, DOC["vocab_size"], n))
            req = server.generate(np.asarray(o.prompt), max_new_tokens=8,
                                  sampling=SamplingParams(greedy=True))
            o.tokens, o.ok = tuple(req.tokens), True
            logits[i] = np.asarray(req.token_logits)
            outcomes.append(o)
    return gqa_decoder_serve_cell.judge_sample(
        cell(), params, outcomes, [], logits, 3)


@pytest.fixture(scope="module")
def sound():
    from lstm_tensorspark_tpu.models import decoder

    cfg = decoder.DecoderConfig.from_model(DOC)
    return decoder, cfg, decoder.init_decoder(7, cfg, dtype=jnp.float32)


def test_the_sound_program_passes(sound):
    _, cfg, params = sound
    judged = serve_and_judge(params, cfg)
    assert judged["ok"] and judged["logit_max"] < 1e-4, judged


@pytest.mark.parametrize("fault", [
    "window_ignored_in_the_sliding_layers", "window_one_too_wide",
    "yarn_in_the_sliding_layers", "plain_rope_in_the_full_layers",
    "attention_factor_dropped", "weights_not_renormalised", "top_7_of_8",
    "query_head_j_reads_kv_head_j_mod_g", "a_recycled_page_read"])
def test_a_fault_fails_the_check(sound, monkeypatch, fault):
    decoder, cfg, params = sound
    from lstm_tensorspark_tpu.ops import paged_attention

    cache_cfg = None
    if fault == "window_ignored_in_the_sliding_layers":
        attend = paged_attention.paged_attention
        monkeypatch.setattr(
            paged_attention, "paged_attention",
            lambda *a, window=None, **k: attend(*a, window=None, **k))
    elif fault == "window_one_too_wide":
        attend = paged_attention.paged_attention
        monkeypatch.setattr(
            paged_attention, "paged_attention",
            lambda *a, window=None, **k: attend(
                *a, window=window and window + 1, **k))
    elif fault == "yarn_in_the_sliding_layers":
        rotate = decoder.rotated_query_key
        monkeypatch.setattr(decoder, "rotated_query_key",
                            lambda q, k, pos, c, kind: rotate(q, k, pos, c, 0))
    elif fault == "plain_rope_in_the_full_layers":
        rotate = decoder.rotated_query_key
        monkeypatch.setattr(decoder, "rotated_query_key",
                            lambda q, k, pos, c, kind: rotate(q, k, pos, c, 1))
    elif fault == "attention_factor_dropped":
        cfg = dataclasses.replace(cfg, rope_attention_factor=1.0)
    elif fault == "weights_not_renormalised":
        cfg = dataclasses.replace(cfg, norm_topk_prob=False)
    elif fault == "top_7_of_8":         # one expert fewer than published
        cfg = dataclasses.replace(cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
    elif fault == "query_head_j_reads_kv_head_j_mod_g":
        def interleaved(q, groups):
            *lead, h, d = q.shape
            return jnp.swapaxes(q.reshape(*lead, h // groups, groups, d), -3, -2)
        monkeypatch.setattr(decoder, "group_of_heads", interleaved)
    elif fault == "a_recycled_page_read":
        # the cache returns pages one page early: the item lists then name a
        # page short of what the window's mask still passes
        cache_cfg = dataclasses.replace(cfg, sliding_window=cfg.sliding_window - 4)
    judged = serve_and_judge(params, cfg, cache_cfg)
    assert not judged["ok"], (fault, judged)
