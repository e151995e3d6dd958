"""The decoder family at tiny widths with the real structure (a dense layer
and two expert layers, 16 experts in 4 groups, top-3 of the best 2 groups,
rope and nope parts, a vocabulary slice), float32 on the CPU: the program
(`models/decoder.py`, `ops/paged_attention.py`, `ops/moe.py`, the paged cache,
the decoder engine, the batcher and the server) against the plain reference
(`benchmark/reference/deepseek_v2.py`, imported from where it lives: one
source of truth), and the bookkeeping around it."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference import deepseek_v2 as reference  # noqa: E402

from lstm_tensorspark_tpu import cli  # noqa: E402
from lstm_tensorspark_tpu.models import decoder  # noqa: E402
from lstm_tensorspark_tpu.ops import moe, paged_attention  # noqa: E402
from lstm_tensorspark_tpu.serve import SamplingParams, ServeServer  # noqa: E402
from lstm_tensorspark_tpu.serve.engine import build_engine  # noqa: E402
from lstm_tensorspark_tpu.serve.state_cache import (  # noqa: E402
    CacheFullError, PagedCache)

GREEDY = SamplingParams(greedy=True)
TINY = os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                    "tiny-decoder.json")
with open(TINY) as f:
    DOC = json.load(f)          # this chip: experts 4-7 of 16, vocabulary 64
CFG = decoder.DecoderConfig.from_model(DOC)
HELD = list(range(CFG.experts_first, CFG.experts_first + CFG.experts_held))


@pytest.fixture(scope="module")
def params():
    return decoder.init_decoder(7, CFG, dtype=jnp.float32)


@pytest.fixture(scope="module")
def engine(params):
    return build_engine(params, CFG, num_slots=8, num_pages=40, page=8,
                        max_context=96, prefill_buckets=(16, 32),
                        batch_buckets=(2, 4), max_prefill_rows=2,
                        interpret=True)


@pytest.fixture(scope="module")
def server(engine):
    srv = ServeServer(engine, max_active=4, queue_size=16,
                      window_ladder=(1, 4), prefill_chunk=32)
    srv.warmup(GREEDY, prompt_lens=(16, 32))
    with srv:
        yield srv


def reference_gaps(params, context, tokens, token_logits):
    """Teacher-force ``context + tokens`` through the reference: (worst
    |program logit - reference logit of the chosen token|, worst lead of
    the reference's largest over the chosen)."""
    seq = np.concatenate([context, tokens[:-1]]).astype(np.int32)
    logits = np.asarray(reference.forward(
        params, DOC, seq, HELD, want=(len(context) - 1, len(seq)),
        block=16, head_group=2))
    chosen = logits[np.arange(len(tokens)), tokens]
    got = np.asarray([c for c, _ in token_logits])
    return float(np.abs(got - chosen).max()), float((logits.max(-1) - chosen).max())


def test_family_seam(engine):
    from lstm_tensorspark_tpu.models import LMConfig
    from lstm_tensorspark_tpu.models.generate import family_of
    from lstm_tensorspark_tpu.serve.decoder_engine import DecoderEngine

    assert family_of(CFG) == "decoder" and family_of(LMConfig(
        vocab_size=8, hidden_size=8, num_layers=1)) == "lstm"
    assert isinstance(engine, DecoderEngine) and engine.family == "decoder"


def test_one_pass_matches_the_reference(params):
    """`forward_tokens` over a whole sequence in one prefill, paged cache
    and kernel included, against the reference's logits at every position."""
    n, page = 24, 8
    tokens = np.random.default_rng(1).integers(2, CFG.vocab_size, n)
    pools = tuple(jnp.zeros((5, page, CFG.latent_width), jnp.float32)
                  for _ in range(CFG.num_hidden_layers))
    pages = [3, 0, 2]
    pos = np.arange(32)
    items = paged_attention.plan_items(
        [pages], [0], [n], page=page, tq=16, tiles=2, capacity=8, scratch_page=4)
    live = pos < n
    hidden, _, counts = decoder.forward_tokens(
        params, decoder.absorb(params, CFG), CFG, pools,
        jnp.asarray(np.pad(tokens, (0, 8))), jnp.asarray(np.where(live, pos, 0)),
        jnp.asarray(live),
        jnp.asarray(np.where(live, np.asarray(pages)[np.minimum(pos, n - 1) // page], 4))[None],
        jnp.asarray(np.where(live, pos % page, 0)),
        [{k: jnp.asarray(v) for k, v in items.items()}],
        tq=paged_attention.PREFILL_TQ, interpret=True)
    got = np.asarray(decoder.head_logits(params, hidden))[:n]
    want = np.asarray(reference.forward(params, DOC, tokens, HELD, want=(0, n),
                                        block=16, head_group=2))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(counts["moe_pairs_total"]) == n * 3 * 2     # live tokens only


def test_chunked_prefill_windows_and_next_turn(server, params, engine):
    """Through `ServeServer.generate`: a prompt longer than the largest
    bucket (prefilled in chunks), decode windows over the paged cache, then
    the kept session's next turn, which prefills only the new tokens on top
    of its pages. Every generated token's logit against the reference's
    full forward pass over the whole conversation."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, CFG.vocab_size, size=41)
    first = server.generate(prompt, max_new_tokens=9, sampling=GREEDY,
                            keep_session=True)
    assert first.error is None and len(first.token_logits) == 9
    slot = engine.cache.lookup(first.session_id)
    assert engine.cache.length[slot] == 41 + 8      # the last token is unconsumed
    held_pages = list(engine.cache.pages_of(slot))
    err, lead = reference_gaps(params, prompt, np.asarray(first.tokens),
                               first.token_logits)
    assert err < 2e-5 and lead < 2e-5
    turn = np.concatenate([[first.tokens[-1]], rng.integers(2, CFG.vocab_size, 5)])
    chunks_before = server.stats()["batcher"]["prefill_tokens_computed"]
    second = server.generate(turn, max_new_tokens=6, sampling=GREEDY,
                             session_id=first.session_id)
    assert second.error is None
    # only the new turn was prefilled, on the pages the session kept
    assert server.stats()["batcher"]["prefill_tokens_computed"] - chunks_before == 6
    context = np.concatenate([prompt, first.tokens[:-1], turn])
    err, lead = reference_gaps(params, context, np.asarray(second.tokens),
                               second.token_logits)
    assert err < 2e-5 and lead < 2e-5
    assert held_pages and first.session_id not in engine.cache   # released at its end


def test_concurrent_sessions_release_every_page(server, engine):
    import threading

    rng = np.random.default_rng(5)
    out = [None] * 6

    def one(i):
        out[i] = server.generate(rng.integers(2, CFG.vocab_size, 5 + 7 * i),
                                 max_new_tokens=4 + i, sampling=GREEDY)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r.error is None and len(r.tokens) == 4 + i
               for i, r in enumerate(out))
    stats = engine.cache.stats()
    assert stats["latent_pages_in_use"] == 0 and stats["live_sessions"] == 0
    assert stats["pages_allocated"] == stats["pages_freed"] > 0
    assert stats["latent_pages_promised"] == 0
    assert engine.counters["moe_pairs_total"] > engine.counters["moe_pairs_here"] > 0


def test_no_compile_after_warmup(server, engine):
    before = engine.num_compiles()
    server.generate(np.arange(2, 40), max_new_tokens=7, sampling=GREEDY)
    assert engine.num_compiles() == before


def test_absorbed_attention_equals_the_decompressed_form(params):
    """The kernel's absorbed scores and sum against explicit ``k_nope`` /
    ``v`` from ``W_kvb``, one decode row over a paged context."""
    rng = np.random.default_rng(2)
    h, nope, rope_d, kv, vd = (CFG.num_attention_heads, CFG.qk_nope_head_dim,
                               CFG.qk_rope_head_dim, CFG.kv_lora_rank,
                               CFG.v_head_dim)
    width, page, n = CFG.latent_width, 8, 19
    layer = params["layers"][1]
    ab = decoder.absorb(params, CFG)[1]
    c_kv, k_pe = rng.normal(size=(n, kv)), rng.normal(size=(n, rope_d))
    q_nope, q_pe = rng.normal(size=(h, nope)), rng.normal(size=(h, rope_d))
    pool = np.zeros((4, page, width), np.float32)
    pages = [2, 0, 1]
    for t in range(n):
        pool[pages[t // page], t % page, :kv + rope_d] = np.concatenate([c_kv[t], k_pe[t]])
    q_cat = np.zeros((1, h, width), np.float32)
    q_cat[0, :, :kv] = np.einsum("hn,hnc->hc", q_nope, np.asarray(ab["w_uk"]))
    q_cat[0, :, kv:kv + rope_d] = q_pe
    items = paged_attention.plan_items([pages], [n - 1], [1], page=page, tq=1,
                                     tiles=1, capacity=4, scratch_page=3)
    ctx = paged_attention.paged_attention(
        jnp.asarray(q_cat), jnp.asarray(pool),
        {k: jnp.asarray(v) for k, v in items.items()},
        scale=CFG.softmax_scale, reading=CFG.reading, name="mla_decode",
        interpret=True)
    got = np.einsum("hc,hcv->hv", np.asarray(ctx)[0], np.asarray(ab["w_uv"]))
    kvb = np.asarray(layer["w_kvb"]).reshape(kv, h, nope + vd)
    k_nope, v = (np.einsum("tc,chd->thd", c_kv, kvb[..., :nope]),
                 np.einsum("tc,chd->thd", c_kv, kvb[..., nope:]))
    s = (np.einsum("hd,thd->ht", q_nope, k_nope)
         + np.einsum("hd,td->ht", q_pe, k_pe)) * CFG.softmax_scale
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("ht,thv->hv", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """The routed parts the four chips compute (each its 4 of the 16
    experts), plus the shared experts and the residual counted ONCE, are the
    uncut layer as the reference computes it with every expert held."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(16, CFG.hidden_size)), jnp.float32)
    key = jax.random.PRNGKey(11)
    base = params["layers"][1]
    full = dict(base,
                w_gate_up=jax.random.normal(key, (16, *base["w_gate_up"].shape[1:])) * 0.1,
                w_down=jax.random.normal(key, (16, *base["w_down"].shape[1:])) * 0.1)
    with jax.default_matmul_precision("highest"):
        uncut = x + reference.mlp(full, DOC, x, list(range(16)), block=16)
    xn = decoder.rmsnorm(x, full["mlp_norm"], CFG.rms_norm_eps)
    total = x + decoder.swiglu(xn, full["shared_gate_up"], full["shared_down"])
    pairs_here = 0
    for first in (0, 4, 8, 12):
        part, counts = moe.routed_experts(
            xn, jnp.ones((16,), bool), full["w_router"],
            full["w_gate_up"][first:first + 4], full["w_down"][first:first + 4],
            first=first, n_group=CFG.n_group, topk_group=CFG.topk_group,
            top_k=CFG.num_experts_per_tok, scale=CFG.routed_scaling_factor,
            renormalise=False, tm=8, interpret=True)
        total = total + part
        pairs_here += int(counts["moe_pairs_here"])
    assert pairs_here == 16 * CFG.num_experts_per_tok     # every pair lands once
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)


def test_router_is_group_limited():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    experts, weights = moe.route(x, w, n_group=4, topk_group=2, top_k=3, scale=16.0,
                                 renormalise=False)
    p = np.asarray(jax.nn.softmax(x @ w, axis=-1))
    assert (np.asarray([len(set(e // 4)) for e in np.asarray(experts)]) <= 2).all()
    np.testing.assert_allclose(np.asarray(weights),
                               16 * np.take_along_axis(p, np.asarray(experts), 1),
                               rtol=1e-5)
    free = np.argsort(-p, axis=1)[:, :3]
    assert (np.sort(free, 1) != np.sort(np.asarray(experts), 1)).any()


def test_dead_rows_route_nowhere():
    x = jnp.ones((8, 16), jnp.float32)
    plan = moe.plan_tiles(jnp.zeros((8, 3), jnp.int32), jnp.arange(8) < 3,
                          first=0, held=4, tm=8)
    assert int(plan["counts"]["moe_pairs_total"]) == 9
    assert int(plan["counts"]["moe_pairs_here"]) == 9
    assert int(plan["counts"]["experts_touched"]) == 1 and int(plan["n_tiles"][0]) == 2
    assert x.shape[0] == 8


def test_yarn_blends_between_the_correction_dimensions():
    inv = decoder.yarn_inv_freq(CFG)
    base = 1.0 / CFG.rope_theta ** (np.arange(0, CFG.qk_rope_head_dim, 2)
                                    / CFG.qk_rope_head_dim)
    assert np.isclose(inv[0], base[0]) and np.isclose(inv[-1], base[-1] / 40)
    assert np.allclose(inv, reference.yarn_inv_freq(DOC))
    assert np.isclose(CFG.softmax_scale, 24 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
    assert np.isclose(CFG.softmax_scale, reference.softmax_scale(DOC))


def test_flops_count_agrees_with_the_parameters_held():
    """`utils/flops.decoder_fwd_flops_per_token` at an even router and no
    context is twice the parameters a token multiplies by: everything held
    but the embedding (a gather), the norms, and the experts it is not
    routed to."""
    from lstm_tensorspark_tpu.utils.flops import decoder_fwd_flops_per_token

    expert = 3 * CFG.hidden_size * CFG.moe_intermediate_size
    moe_layers = CFG.num_hidden_layers - CFG.first_k_dense_replace
    norms = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        jax.eval_shape(lambda: decoder.init_decoder(0, CFG))) if x.ndim == 1)
    used = (decoder.param_count(CFG) - norms
            - CFG.vocab_size * CFG.hidden_size
            - moe_layers * (CFG.experts_held - 3 * 4 / 16) * expert)
    assert decoder_fwd_flops_per_token(CFG) == pytest.approx(2 * used)
    assert decoder_fwd_flops_per_token(CFG, context=10) > 2 * used


# ---- the paged cache's bookkeeping -----------------------------------------

def small_cache():
    return PagedCache(3, 4, [decoder.PageKind("latent", (0,), 6, 128)],
                      jnp.float32)


def test_pages_follow_the_session():
    cache = small_cache()
    slot, fresh = cache.acquire_pinned("a")
    assert fresh and cache.length[slot] == 0
    cache.commit(slot, 10)                              # 3 pages promised
    assert cache.stats()["latent_pages_promised"] == 3
    assert len(cache.ensure(slot, 5)[0][1]) == 2 and cache.pages_in_use == 2
    assert cache.stats()["latent_pages_promised"] == 1
    cache.length[slot] = 5
    cache.unpin("a")                                    # kept: pages stay
    assert cache.stats()["latent_pages_promised"] == 0 and cache.pages_in_use == 2
    assert cache.acquire_pinned("a") == (slot, False)
    cache.release("a")
    stats = cache.stats()
    assert stats["latent_pages_in_use"] == 0 and stats["pages_freed"] == 2
    assert "a" not in cache and len(cache) == 0


def test_admission_is_by_pages():
    cache = small_cache()
    a, _ = cache.acquire_pinned("a")
    cache.commit(a, 16)                                 # 4 of 6 pages
    assert cache.can_commit([(None, 8)]) and not cache.can_commit([(None, 9)])
    assert not cache.can_commit([(None, 4), (None, 8)])
    b, _ = cache.acquire_pinned("b")
    with pytest.raises(CacheFullError):
        cache.commit(b, 12)
    cache.release("a")
    cache.commit(b, 12)
    for sid in ("c", "d"):
        cache.acquire(sid)
    with pytest.raises(CacheFullError):                 # slots are not evicted
        cache.acquire("e")


def test_a_request_that_cannot_fit_fails_loudly(server, engine):
    with pytest.raises(RuntimeError, match="does not fit|tokens"):
        server.generate(np.arange(2, 60), max_new_tokens=300, sampling=GREEDY)
    assert engine.cache.stats()["latent_pages_in_use"] == 0


def test_sampled_decoding_is_refused(engine):
    with pytest.raises(ValueError, match="greedily"):
        engine.prefill([(engine.cache.scratch_slot, True, np.arange(4))],
                       SamplingParams(temperature=0.7))


# ---- `cli serve --model-file` ------------------------------------------------

DECODER_FLAGS = ["--model-file", TINY, "--weights-dtype", "float32",
                 "--interpret-kernels", "--greedy",
                 "--page-size", "8", "--max-context", "128",
                 "--latent-pool-gib", "0.001", "--prefill-buckets", "16,32",
                 "--batch-buckets", "2,4", "--prefill-rows", "2",
                 "--max-active", "4", "--num-slots", "8", "--decode-window", "4"]


@pytest.mark.parametrize("flags,named", [
    (["--prefix-cache", "on"], "--prefix-cache on"),
    (["--prefix-fabric", "on"], "--prefix-fabric on"),
    (["--tiered-cache", "on"], "--tiered-cache on"),
    (["--session-dir", "/tmp/x"], "--session-dir"),
    (["--speculative"], "--speculative"),
    (["--mesh-shards", "2"], "--mesh-shards"),
    (["--replicas", "2"], "--replicas"),
    (["--checkpoint-dir", "/tmp/x"], "--checkpoint-dir"),
    (["--registry-dir", "/tmp/x"], "--registry-dir"),
    (["--autotune", "on"], "--autotune on"),
    (["--decode-kernel", "pallas"], "--decode-kernel"),
    (["--temperature", "0.7"], "sampled decoding"),
])
def test_lstm_only_flags_are_refused_with_a_decoder(flags, named):
    argv = [a for a in DECODER_FLAGS if not (named.startswith("sampled")
                                             and a == "--greedy")] + flags
    args = cli.build_serve_parser().parse_args(["--selftest", *argv])
    with pytest.raises(SystemExit) as e:
        cli._build_serve_stack(args, 2 if "--replicas" in flags else 1)
    assert named in str(e.value) and "LSTM-only" in str(e.value)


def test_lstm_defaults_keep_their_caches():
    args = cli.build_serve_parser().parse_args(["--selftest"])
    _, _, server = cli._build_serve_stack(args, 1)
    assert server.engine.prefix is not None and server.engine.tiers is not None
    assert server.engine.family == "lstm" and server.engine.admits(None)


def test_cli_selftest_serves_the_decoder(capsys):
    rc = cli._run_serve(["--selftest", *DECODER_FLAGS, "--sessions", "3",
                         "--max-new-tokens", "6"])
    out = capsys.readouterr().out
    assert rc == 0 and "serve selftest: PASS" in out
    line = json.loads(next(x for x in out.splitlines() if x.startswith("{")))
    assert line["family"] == "decoder" and line["cache"]["latent_pages_in_use"] == 0
    assert line["moe_pairs_here"] > 0 and line["decode_steps"] > 0
