"""The second decoder block (grouped-query attention, window and full layers
mixed, renormalised top-k experts, all held) at tiny widths with the real
structure: one period of (sliding, sliding, sliding, full), a window of 8
tokens, pages of 4, 8 experts top-2, float32 on the CPU. The program
(`models/decoder.py`, `ops/paged_attention.py`, `ops/moe.py`, the paged cache
with its two kinds of page, the decoder engine, the batcher and the server)
against the plain reference (`benchmark/reference/mellum2.py`, imported from
where it lives), on contexts several windows long, so that window pages are
returned while the sessions run."""

import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference import mellum2 as reference  # noqa: E402

from lstm_tensorspark_tpu import cli  # noqa: E402
from lstm_tensorspark_tpu.models import decoder  # noqa: E402
from lstm_tensorspark_tpu.ops import moe, paged_attention  # noqa: E402
from lstm_tensorspark_tpu.serve import SamplingParams, ServeServer  # noqa: E402
from lstm_tensorspark_tpu.serve.engine import build_engine  # noqa: E402
from lstm_tensorspark_tpu.serve.state_cache import (  # noqa: E402
    CacheFullError, PagedCache)

GREEDY = SamplingParams(greedy=True)
TINY = os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                    "tiny-mellum.json")
with open(TINY) as f:
    DOC = json.load(f)
CFG = decoder.DecoderConfig.from_model(DOC)
WINDOW, PAGE = CFG.sliding_window, 4


@pytest.fixture(scope="module")
def params():
    return decoder.init_decoder(7, CFG, dtype=jnp.float32)


@pytest.fixture(scope="module")
def engine(params):
    return build_engine(params, CFG, num_slots=8, num_pages=(60, 24),
                        page=PAGE, max_context=96, prefill_buckets=(16, 32),
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
        params, DOC, seq, want=(len(context) - 1, len(seq)), block=8))
    chosen = logits[np.arange(len(tokens)), tokens]
    got = np.asarray([c for c, _ in token_logits])
    return float(np.abs(got - chosen).max()), float((logits.max(-1) - chosen).max())


def poison_free_pages(engine):
    """Overwrite every page no session holds (the scratch pages too) with
    1e4: a program that reads one shows it in every later logit. (Finite:
    the kernel multiplies the masked rows of a page a session holds in part
    by an exact 0, which only a NaN or an infinity would survive.)"""
    cache = engine.cache
    pools = list(cache.pools)
    for k, kind in enumerate(cache.kinds):
        ids = np.asarray(cache.free_page_ids(k) + [kind.num_pages])
        for i in kind.layers:
            pools[i] = pools[i].at[ids].set(1e4)
    cache.swap(pools)


# ---- the configuration -------------------------------------------------------

def test_the_published_keys_are_read_as_published():
    assert CFG.grouped and CFG.model_type == "mellum"
    assert CFG.layer_kinds == (1, 1, 1, 0) and CFG.sliding_window == 8
    assert (CFG.n_routed_experts, CFG.experts_held, CFG.experts_first) == (8, 8, 0)
    assert CFG.norm_topk_prob and CFG.n_group == 1 and CFG.n_shared_experts == 0
    assert CFG.rope_attention_factor == pytest.approx(0.1 * np.log(16) + 1)
    assert CFG.softmax_scale == pytest.approx(16 ** -0.5)
    kinds = decoder.cache_kinds(CFG, (5, 3))
    assert [(k.name, k.layers, k.num_pages, k.window) for k in kinds] == [
        ("full", (3,), 5, None), ("window", (0, 1, 2), 3, 8)]
    assert CFG.latent_width == 2 * 2 * 16       # k and v of both key/value heads
    with pytest.raises(ValueError, match="page counts"):
        decoder.cache_kinds(CFG, 5)


def test_the_benchmarks_file_holds_what_it_says():
    """`benchmark/configs/mellum2-12b-l8.json` at its published widths:
    3,794,966,784 parameters (8 layers of 417,747,456, embedding and head of
    226,492,416 each, the final norm), two whole periods in their order."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-l8.json")) as f:
        doc = json.load(f)
    cfg = decoder.DecoderConfig.from_model(doc)
    assert decoder.param_count(cfg) == doc["parameters_held"] == 3_794_966_784
    assert cfg.layer_kinds == (1, 1, 1, 0) * 2 and cfg.sliding_window == 1024
    assert cfg.latent_width == 1024 and cfg.reading.heads == 8
    assert (cfg.n_routed_experts, cfg.experts_held) == (64, 64)
    both = {True: DOC, False: json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "deepseek-v2-ep4.json")))}
    for renormalised, d in both.items():     # read, not assumed
        assert decoder.DecoderConfig.from_model(d).norm_topk_prob is renormalised
        assert d["norm_topk_prob"] is renormalised


def test_yarn_only_in_the_full_layers():
    plain = decoder.plain_inv_freq(CFG)
    assert np.allclose(plain, reference.inv_freq(DOC, "sliding_attention")[0])
    yarn, factor = reference.inv_freq(DOC, "full_attention")
    assert np.allclose(decoder.yarn_inv_freq(CFG), yarn)
    assert factor == CFG.rope_attention_factor
    assert np.isclose(yarn[0], plain[0]) and np.isclose(yarn[-1], plain[-1] / 16)


# ---- the kernel and the router ------------------------------------------------

@pytest.mark.parametrize("window", [None, 8, 5])
def test_grouped_attention_against_plain_softmax(window):
    """One decode row and one prefill tile over a paged context whose page
    list starts late (``bases``), against a plain masked softmax."""
    rng = np.random.default_rng(2)
    g, hq, d, page, n = 2, 4, 16, 4, 23
    rd = paged_attention.grouped(g, g * hq, d)
    k, v = rng.normal(size=(n, g, d)), rng.normal(size=(n, g, d))
    pool = np.zeros((8, page, rd.width), np.float32)
    base = 0 if window is None else max(n - 1 - 15 - (window - 1), 0) // page
    pages = [5, 0, 2, 6, 1, 3][: -(-n // page) - base]
    for t in range(base * page, n):
        pool[pages[t // page - base], t % page] = np.concatenate(
            [k[t].ravel(), v[t].ravel()])
    for tq, start in ((1, n - 1), (16, n - 16)):
        q = rng.normal(size=(tq, g * hq, d))
        items = paged_attention.plan_items(
            [pages], [start], [tq], page=page, tq=tq, tiles=1, capacity=8,
            scratch_page=7, window=window, bases=[base])
        q_tile = np.swapaxes(q.reshape(tq, g, hq, d), 0, 1).reshape(1, -1, d)
        got = np.asarray(paged_attention.paged_attention(
            jnp.asarray(q_tile, jnp.float32), jnp.asarray(pool),
            {k_: jnp.asarray(v_) for k_, v_ in items.items()}, scale=0.25,
            reading=rd, window=window, name="gqa_decode", interpret=True))
        got = np.swapaxes(got.reshape(g, tq, hq, d), 0, 1)
        for i in range(tq):
            p_i = start + i
            lo = 0 if window is None else max(p_i - window + 1, 0)
            for j in range(g * hq):
                s = k[lo:p_i + 1, j // hq] @ q[i, j] * 0.25
                w = np.exp(s - s.max())
                want = (w / w.sum()) @ v[lo:p_i + 1, j // hq]
                np.testing.assert_allclose(got[i, j // hq, j % hq], want,
                                           atol=2e-5)


def test_a_window_layers_items_name_only_pages_in_a_window():
    items = paged_attention.plan_items(
        [list(range(100, 110))], [24], [16], page=4, tq=16, tiles=1,
        capacity=16, scratch_page=99, window=8, bases=[3])
    n = int(items["n"][0])
    # queries 24..39 see keys 17..39: page indices 4..9 of a list from 3
    assert list(items["page"][:n]) == [101, 102, 103, 104, 105, 106]
    assert list(items["start"][:n]) == [16, 20, 24, 28, 32, 36]
    everything = paged_attention.plan_items(
        [list(range(100, 110))], [24], [16], page=4, tq=16, tiles=1,
        capacity=16, scratch_page=99)
    assert int(everything["n"][0]) == 10


@pytest.mark.parametrize("renormalise", [False, True])
def test_route_against_a_plain_router(renormalise):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    experts, weights = moe.route(x, w, n_group=1, topk_group=1, top_k=2,
                                 scale=1.0, renormalise=renormalise)
    logits = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=1)[:, :2]
    picked = np.take_along_axis(p, top, 1)
    if renormalise:
        picked = picked / picked.sum(-1, keepdims=True)
    assert (np.asarray(experts) == top).all()
    np.testing.assert_allclose(np.asarray(weights), picked, rtol=1e-5)
    assert np.allclose(np.asarray(weights).sum(-1), 1.0) == renormalise


# ---- the model through the cache -----------------------------------------------

def test_one_pass_matches_the_reference(params):
    """`forward_tokens` over a whole sequence of five windows in one prefill,
    both kinds of page and the kernels included, against the reference's
    logits at every position."""
    n, t = 40, 48
    tokens = np.random.default_rng(1).integers(2, CFG.vocab_size, n)
    kinds = decoder.cache_kinds(CFG, (12, 12))
    pools = tuple(jnp.zeros((13, PAGE, CFG.latent_width), jnp.float32)
                  for _ in range(CFG.num_hidden_layers))
    pages = list(range(10))[::-1]
    pos = np.arange(t)
    live = pos < n
    items = [paged_attention.plan_items(
        [pages], [0], [n], page=PAGE, tq=16, tiles=3, capacity=40,
        scratch_page=12, window=k.window) for k in kinds]
    assert int(items[1]["n"][0]) < int(items[0]["n"][0])
    write = np.where(live, np.asarray(pages)[np.minimum(pos, n - 1) // PAGE], 12)
    hidden, _, counts = decoder.forward_tokens(
        params, decoder.absorb(params, CFG), CFG, pools,
        jnp.asarray(np.pad(tokens, (0, t - n))), jnp.asarray(np.where(live, pos, 0)),
        jnp.asarray(live), jnp.asarray(np.stack([write, write])),
        jnp.asarray(np.where(live, pos % PAGE, 0)),
        [{k: jnp.asarray(v) for k, v in it.items()} for it in items],
        tq=paged_attention.PREFILL_TQ, interpret=True)
    got = np.asarray(decoder.head_logits(params, hidden))[:n]
    want = np.asarray(reference.forward(params, DOC, tokens, want=(0, n), block=8))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(counts["moe_pairs_total"]) == int(counts["moe_pairs_here"]) == n * 2 * 4


def test_chunked_prefill_windows_and_next_turn(server, params, engine):
    """Through `ServeServer.generate`: a prompt of five windows, longer than
    the largest bucket (prefilled in chunks ACROSS window boundaries), decode
    windows over both kinds of page, then the kept session's next turn on
    top of the pages it still holds. Window pages go back to the free list
    while the session runs, every page no session holds is POISONED before
    the next turn, and every generated token's logit is held to the
    reference's full forward pass over the whole conversation."""
    cache = engine.cache
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, CFG.vocab_size, size=41)
    recycled = cache.stats()["window_pages_recycled"]
    first = server.generate(prompt, max_new_tokens=11, sampling=GREEDY,
                            keep_session=True)
    assert first.error is None and len(first.token_logits) == 11
    slot = cache.lookup(first.session_id)
    length = 41 + 10                           # the last token is unconsumed
    assert cache.length[slot] == length
    assert cache.stats()["window_pages_recycled"] - recycled >= 9
    full, (base, window_pages) = cache.held(slot, 0), cache.held(slot, 1)
    assert full[0] == 0 and len(full[1]) == cache.pages_for(length)
    assert base == (length - (WINDOW - 1)) // PAGE
    assert base + len(window_pages) >= cache.pages_for(length)
    assert len(window_pages) <= 4
    err, lead = reference_gaps(params, prompt, np.asarray(first.tokens),
                               first.token_logits)
    assert err < 2e-5 and lead < 2e-5
    poison_free_pages(engine)
    turn = np.concatenate([[first.tokens[-1]], rng.integers(2, CFG.vocab_size, 5)])
    second = server.generate(turn, max_new_tokens=9, sampling=GREEDY,
                             session_id=first.session_id)
    assert second.error is None
    context = np.concatenate([prompt, first.tokens[:-1], turn])
    err, lead = reference_gaps(params, context, np.asarray(second.tokens),
                               second.token_logits)
    assert err < 2e-5 and lead < 2e-5
    assert first.session_id not in cache          # released at its end
    counts = engine.stats()["decoder"]
    assert counts["decode_full_keys_read"] > counts["decode_window_keys_read"] > 0
    assert counts["prefill_full_pairs"] > counts["prefill_window_pairs"] > 0


def test_sessions_of_unequal_length_in_one_dispatch(server, params, engine):
    """Six concurrent sessions, 5 to 40 tokens of prompt: rows of unequal
    length share prefill dispatches and decode windows; each is held to the
    reference, and every page of both kinds comes back."""
    poison_free_pages(engine)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, CFG.vocab_size, 5 + 7 * i) for i in range(6)]
    out = [None] * 6

    def one(i):
        out[i] = server.generate(prompts[i], max_new_tokens=4 + 2 * i,
                                 sampling=GREEDY)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r.error is None and len(r.tokens) == 4 + 2 * i
               for i, r in enumerate(out))
    for prompt, r in zip(prompts, out):
        err, lead = reference_gaps(params, prompt, np.asarray(r.tokens),
                                   r.token_logits)
        assert err < 2e-5 and lead < 2e-5
    stats = engine.cache.stats()
    assert stats["full_pages_in_use"] == stats["window_pages_in_use"] == 0
    assert stats["full_pages_promised"] == stats["window_pages_promised"] == 0
    assert stats["pages_allocated"] == stats["pages_freed"] > 0
    assert stats["live_sessions"] == 0
    assert engine.stats()["decoder"]["decode_experts_touched"] > 0


def test_no_compile_after_warmup(server, engine):
    before = engine.num_compiles()
    server.generate(np.arange(2, 47), max_new_tokens=7, sampling=GREEDY)
    assert engine.num_compiles() == before


def test_an_engine_that_keeps_too_few_window_pages_is_caught(params):
    """The reason the cache's window is the model's: an engine whose cache
    returns pages a query still sees (a window one page short) parts from
    the reference as soon as a context outgrows it."""
    import dataclasses

    short = dataclasses.replace(CFG, sliding_window=WINDOW - PAGE)
    engine = build_engine(params, CFG, num_slots=4, num_pages=(30, 12),
                          page=PAGE, max_context=64, prefill_buckets=(16, 32),
                          batch_buckets=(2,), max_prefill_rows=2, interpret=True)
    engine.cache.kinds = decoder.cache_kinds(short, (30, 12))
    prompt = np.random.default_rng(3).integers(2, CFG.vocab_size, 30)
    with ServeServer(engine, max_active=2, window_ladder=(1, 4),
                     prefill_chunk=32) as srv:
        r = srv.generate(prompt, max_new_tokens=6, sampling=GREEDY)
    err, _ = reference_gaps(params, prompt, np.asarray(r.tokens), r.token_logits)
    assert err > 1e-3


# ---- the cache's bookkeeping: two kinds of page -----------------------------

def small_cache(full=8, window=6, slots=3):
    kinds = [decoder.PageKind("full", (1,), full, 128),
             decoder.PageKind("window", (0,), window, 128, window=8)]
    return PagedCache(slots, 4, kinds, jnp.float32, grow_step=8)


def test_window_pages_follow_the_sessions_last_tokens():
    cache = small_cache()
    assert cache.window_cap(1) == 5 and len(cache.pools) == 2
    slot, _ = cache.acquire_pinned("a")
    cache.commit(slot, 30)                     # 8 full pages, 5 window pages
    stats = cache.stats()
    assert (stats["full_pages_promised"], stats["window_pages_promised"]) == (8, 5)
    held = []
    for upto in (8, 16, 24, 30):               # grow 8 tokens at a time
        (_, full), (base, window) = cache.ensure(slot, upto)
        cache.length[slot] = upto
        held.append((len(full), base, len(window)))
    # a query at `length` sees keys from length - 7: earlier pages are gone
    assert held == [(2, 0, 2), (4, 0, 4), (6, 2, 4), (8, 4, 4)]
    stats = cache.stats()
    assert stats["window_pages_recycled"] == 4 and stats["pages_freed"] == 4
    assert stats["window_pages_in_use"] == 4 and stats["full_pages_in_use"] == 8
    assert stats["window_pages_promised"] == 1      # may hold 5 while it runs
    cache.unpin("a")                           # idle: what its length implies
    assert cache.held(slot, 1) == (5, cache.held(slot, 1)[1])
    assert len(cache.held(slot, 1)[1]) == 3 and len(cache.pages_of(slot)) == 8
    assert cache.stats()["window_pages_promised"] == 0
    with pytest.raises(ValueError, match="at once"):
        cache.ensure(slot, 30 + 9)
    cache.release("a")
    stats = cache.stats()
    assert stats["full_pages_in_use"] == stats["window_pages_in_use"] == 0
    assert stats["pages_allocated"] == stats["pages_freed"] == 8 + 8
    assert sorted(cache.free_page_ids(1)) == list(range(6))


@pytest.mark.parametrize("kind", ["full", "window"])
def test_admission_needs_pages_of_both_kinds(kind):
    """A request is refused when EITHER kind of page runs out, and admitted
    once a session is released."""
    cache = small_cache(full=8, window=12) if kind == "full" \
        else small_cache(full=40, window=6)
    a, _ = cache.acquire_pinned("a")
    cache.commit(a, 20)                        # 5 full, 5 window
    assert cache.can_commit([(None, 4)])       # one page of each is left
    assert not cache.can_commit([(None, 16)])
    assert not cache.can_commit([(None, 4), (None, 4)]) or kind == "full"
    b, _ = cache.acquire_pinned("b")
    with pytest.raises(CacheFullError, match=kind + " pages"):
        cache.commit(b, 16)
    assert cache.stats()[f"{kind}_pages_promised"] == 5   # nothing half-promised
    cache.release("a")
    cache.commit(b, 16)
    assert cache.can_commit([(None, 4)])


def test_a_request_waits_for_window_pages_and_then_runs(params):
    """Through the batcher: with window pages for one running session only,
    a second request waits in the queue until the first ends, and both
    agree with the reference."""
    engine = build_engine(params, CFG, num_slots=4, num_pages=(40, 11),
                          page=PAGE, max_context=64, prefill_buckets=(16, 32),
                          batch_buckets=(2,), max_prefill_rows=2, interpret=True)
    assert engine.cache.window_cap(1) == 11
    rng = np.random.default_rng(8)
    prompts = [rng.integers(2, CFG.vocab_size, 45) for _ in range(2)]
    out = [None, None]
    with ServeServer(engine, max_active=2, window_ladder=(1, 4),
                     prefill_chunk=32) as srv:
        def one(i):
            out[i] = srv.generate(prompts[i], max_new_tokens=5, sampling=GREEDY)
        threads = [threading.Thread(target=one, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for prompt, r in zip(prompts, out):
        assert r.error is None
        err, _ = reference_gaps(params, prompt, np.asarray(r.tokens), r.token_logits)
        assert err < 2e-5
    assert engine.cache.pages_in_use == 0


# ---- `cli serve --model-file` ------------------------------------------------

KV_FLAGS = ["--model-file", TINY, "--weights-dtype", "float32",
            "--interpret-kernels", "--greedy", "--page-size", "4",
            "--max-context", "128", "--kv-pool-gib", "0.0004",
            "--kv-window-gib", "0.0002", "--prefill-buckets", "16,32",
            "--batch-buckets", "2,4", "--prefill-rows", "2", "--max-active", "4",
            "--num-slots", "8", "--decode-window", "4"]


def test_one_engine_class_serves_both_files():
    from lstm_tensorspark_tpu.serve.decoder_engine import DecoderEngine

    args = cli.build_serve_parser().parse_args(["--selftest", *KV_FLAGS])
    _, cfg, server = cli._build_serve_stack(args, 1)
    assert type(server.engine) is DecoderEngine and cfg.grouped
    assert [k.name for k in server.engine.cache.kinds] == ["full", "window"]
    # 0.0002 GiB each, in pages of 4 tokens x 64 lanes x 2 bytes x (1 full
    # | 3 window) layers
    stats = server.engine.cache.stats()
    assert (stats["full_pages_total"], stats["window_pages_total"]) == (419, 139)


@pytest.mark.parametrize("flags,named", [
    (["--prefix-cache", "on"], "--prefix-cache on"),
    (["--tiered-cache", "on"], "--tiered-cache on"),
    (["--speculative"], "--speculative"),
    (["--replicas", "2"], "--replicas"),
    (["--temperature", "0.7"], "sampled decoding"),
])
def test_lstm_only_flags_are_refused_with_the_kv_decoder(flags, named):
    argv = [a for a in KV_FLAGS if not (named.startswith("sampled")
                                        and a == "--greedy")] + flags
    args = cli.build_serve_parser().parse_args(["--selftest", *argv])
    with pytest.raises(SystemExit) as e:
        cli._build_serve_stack(args, 2 if "--replicas" in flags else 1)
    assert named in str(e.value) and "LSTM-only" in str(e.value)


def test_the_window_pool_has_no_default_size():
    argv = [a for i, a in enumerate(KV_FLAGS)
            if "--kv-window-gib" not in (a, KV_FLAGS[i - 1])]
    args = cli.build_serve_parser().parse_args(["--selftest", *argv])
    with pytest.raises(SystemExit) as e:
        cli._build_serve_stack(args, 1)
    assert "--kv-window-gib" in str(e.value)


def test_cli_selftest_serves_the_kv_decoder(capsys):
    rc = cli._run_serve(["--selftest", *KV_FLAGS, "--sessions", "3",
                         "--max-new-tokens", "6"])
    out = capsys.readouterr().out
    assert rc == 0 and "serve selftest: PASS" in out
    line = json.loads(next(x for x in out.splitlines() if x.startswith("{")))
    assert line["family"] == "decoder"
    assert line["cache"]["full_pages_in_use"] == line["cache"]["window_pages_in_use"] == 0
    assert line["cache"]["window_pages_recycled"] > 0
    assert line["moe_pairs_here"] == line["moe_pairs_total"] > 0
