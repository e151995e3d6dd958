"""Lossless speculative decoding (serve/engine.py ``attach_draft`` /
``spec_window`` / ``spec_window_next`` + serve/batcher.py speculative
scheduling + serve/autotune.py's spec_k knob).

The contract under test:

- greedy speculative output is TOKEN-IDENTICAL to plain greedy decode
  (scan AND Pallas verify windows) no matter how bad the draft is — the
  target verifies every proposal in one teacher-forced pass, so draft
  quality only moves the acceptance rate, never a token;
- O(1) rollback: an ALL-REJECT speculative step (a crafted draft whose
  argmax never matches the target's) leaves engine state — the h/c slot
  rows, the session cursor, the prefix cache — bitwise-identical to
  never speculating, including across a SessionTiers spill/promote round
  trip;
- the spec compile lattice stays bounded and replay-zero, and moving
  K_draft across warmed spec-ladder rungs (``set_spec_k`` — exactly the
  autotuner's move) costs zero mid-traffic compiles;
- the autotuner's spec_k law: saturating acceptance walks K up (slow,
  patience_up), wasted verify depth walks it down fast (patience_down),
  and rung 0 = plain decode re-probes only on live decode-traffic
  evidence (at rung 0 no acceptance evidence can ever accumulate).
"""

import jax
import numpy as np
import pytest

from lstm_tensorspark_tpu.models import LMConfig, init_lm, make_generate_fn
from lstm_tensorspark_tpu.serve import (
    PAD_TOKEN,
    AutoTuneConfig,
    AutoTuner,
    Batcher,
    Request,
    ServeEngine,
    ServeServer,
)
from lstm_tensorspark_tpu.train.distill import draft_config

_CFG = LMConfig(vocab_size=37, hidden_size=16, num_layers=2)
_DCFG = draft_config(_CFG)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 37, size=n).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return init_lm(jax.random.PRNGKey(11), _CFG)


@pytest.fixture(scope="module")
def draft_params():
    """An UNDISTILLED (random-init) draft: token parity must hold for it
    exactly as for a distilled one — only acceptance differs."""
    return init_lm(jax.random.PRNGKey(5), _DCFG)


def _wrong_draft(avoid_tokens):
    """A draft whose argmax is a CONSTANT token the target never emits:
    zero weights everywhere, one spiked head bias — so every proposal is
    rejected and every spec window emits exactly the one correction
    token (the all-reject worst case the rollback property needs)."""
    wrong = next(t for t in range(_CFG.vocab_size)
                 if t not in set(int(x) for x in avoid_tokens))
    zeros = jax.tree_util.tree_map(np.zeros_like,
                                   init_lm(jax.random.PRNGKey(0), _DCFG))
    bias = np.zeros((_CFG.vocab_size,), np.float32)
    bias[wrong] = 10.0
    zeros["head"]["bias"] = bias
    return zeros, wrong


def _engine(params, **kw):
    kw.setdefault("num_slots", 8)
    kw.setdefault("prefill_buckets", (4, 8))
    kw.setdefault("batch_buckets", (1, 2, 4))
    return ServeEngine(params, _CFG, **kw)


def _ref(params, prompt, n_new):
    gen = make_generate_fn(_CFG, max_new_tokens=n_new, greedy=True)
    return [int(t) for t in np.asarray(
        gen(params, prompt[None, :], jax.random.PRNGKey(0)))[0, prompt.size:]]


def _spec_stream(engine, slot, first_tok, n_new, k_draft):
    """Chain fresh spec windows until ``n_new`` tokens emitted; returns
    (tokens, emitted-per-window)."""
    out, per_window = [int(first_tok)], []
    while len(out) < n_new:
        win = engine.spec_window([slot], [out[-1]],
                                 [n_new - len(out)], k_draft=k_draft)
        row = ServeEngine.fetch_window(win)[0]
        emitted = [int(t) for t in row if int(t) != PAD_TOKEN]
        assert emitted, row
        per_window.append(len(emitted))
        out.extend(emitted)
    return out[:n_new], per_window


# ---- greedy token parity (the lossless claim) ----------------------------


def test_spec_engine_greedy_matches_generate(params, draft_params):
    """Engine-level chained spec windows == models/generate.py, with a
    random (undistilled) draft — parity is by construction, not by
    draft quality."""
    engine = _engine(params)
    engine.attach_draft(draft_params, _DCFG, version=1)
    p = _prompt(4, 1)
    n_new = 12
    slot, _ = engine.cache.acquire("s")
    first, _ = engine.prefill([(slot, True, p)])
    got, _ = _spec_stream(engine, slot, first[0], n_new, k_draft=2)
    assert got == _ref(params, p, n_new)


def test_spec_window_next_pipelined_parity(params, draft_params):
    """The dispatch-ahead spec chain (spec_window_next from device
    handles, K_draft moved mid-stream like the autotuner would) stays
    token-identical to the reference."""
    engine = _engine(params)
    engine.attach_draft(draft_params, _DCFG, version=1)
    p = _prompt(5, 2)
    slot, _ = engine.cache.acquire("s")
    first, _ = engine.prefill([(slot, True, p)])
    out = [int(first[0])]
    win = engine.spec_window([slot], [out[0]], [32], k_draft=2)
    nxt = engine.spec_window_next(win, k_draft=4)  # knob move mid-chain
    for w in (win, nxt):
        row = ServeEngine.fetch_window(w)[0]
        out.extend(int(t) for t in row if int(t) != PAD_TOKEN)
    assert out[: len(out)] == _ref(params, p, 32)[: len(out)]


def test_spec_batcher_greedy_parity_and_windows_dispatched(params,
                                                           draft_params):
    """Scheduler-level: a speculative Batcher serves token-identical
    greedy output AND actually dispatches spec windows (parity alone
    could pass with speculation inert)."""
    engine = _engine(params)
    engine.attach_draft(draft_params, _DCFG, version=1)
    batcher = Batcher(engine, max_active=4, queue_size=16,
                      speculative=True, spec_ladder=(2, 4))
    reqs = [Request(_prompt(3 + i, 7 + i), 14) for i in range(3)]
    for r in reqs:
        batcher.submit(r)
    batcher.drain()
    for i, r in enumerate(reqs):
        assert r.error is None
        assert r.tokens == _ref(params, _prompt(3 + i, 7 + i), 14)
    assert sum(batcher.spec_windows_dispatched.values()) > 0


def test_spec_pallas_window_matches_scan(params, draft_params):
    """The fused Pallas verify window (interpret mode off-TPU) is
    token-identical to the scan spec window — and actually ran (the
    compile-count key proves it was not a silent scan fallback)."""
    scan_eng = _engine(params)
    scan_eng.attach_draft(draft_params, _DCFG, version=1)
    pallas_eng = _engine(params, decode_kernel="pallas")
    pallas_eng.attach_draft(draft_params, _DCFG, version=1)
    p = _prompt(4, 3)
    n_new = 10
    streams = {}
    for name, engine in (("scan", scan_eng), ("pallas", pallas_eng)):
        slot, _ = engine.cache.acquire("s")
        first, _ = engine.prefill([(slot, True, p)])
        streams[name], _ = _spec_stream(engine, slot, first[0], n_new,
                                        k_draft=2)
    assert streams["pallas"] == streams["scan"] == _ref(params, p, n_new)
    assert any(k[0] == "spec_window_pallas"
               for k in pallas_eng.compile_counts), (
        dict(pallas_eng.compile_counts))


# ---- O(1) rollback: the all-reject property ------------------------------


def test_all_reject_spec_state_bitwise_identical(params):
    """EVERY proposal rejected: each spec window must emit exactly one
    token (the target's correction), the stream must equal plain greedy
    decode, and the committed h/c slot state must be BITWISE identical
    to an engine that never speculated — the O(1)-rollback property
    (neither model's carry ever latched past the last emission, so
    rejection costs nothing to undo)."""
    p = _prompt(4, 9)
    n_new = 8
    ref = _ref(params, p, n_new)
    wrong_draft, wrong_tok = _wrong_draft(ref)

    spec_eng = _engine(params)
    spec_eng.attach_draft(wrong_draft, _DCFG, version=1)
    plain_eng = _engine(params)

    sslot, _ = spec_eng.cache.acquire("s")
    pslot, _ = plain_eng.cache.acquire("s")
    sfirst, _ = spec_eng.prefill([(sslot, True, p)])
    pfirst, _ = plain_eng.prefill([(pslot, True, p)])
    assert int(sfirst[0]) == int(pfirst[0]) == ref[0]

    spec_got, per_window = _spec_stream(spec_eng, sslot, sfirst[0], n_new,
                                        k_draft=2)
    assert spec_got == ref
    # all-reject: every window emitted ONLY its correction token
    assert per_window == [1] * (n_new - 1), per_window
    assert wrong_tok not in spec_got

    plain_got = [int(pfirst[0])]
    while len(plain_got) < n_new:
        win = plain_eng.decode_window([pslot], [plain_got[-1]],
                                      [n_new - len(plain_got)], window=1)
        row = ServeEngine.fetch_window(win)[0]
        plain_got.extend(int(t) for t in row if int(t) != PAD_TOKEN)
    assert plain_got == ref

    sh, sc = spec_eng.cache.read_slots([sslot])
    ph, pc = plain_eng.cache.read_slots([pslot])
    np.testing.assert_array_equal(np.asarray(sh), np.asarray(ph))
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(pc))


def test_all_reject_rollback_bitwise_across_tiers(params):
    """The rollback property survives SessionTiers spill/promote: one
    device slot, two sessions ping-ponging through the host tier (every
    switch LRU-evicts one session into the spill worker, every return
    promotes it through the fill path). Final detached states must be
    BITWISE identical between the all-reject speculative engine and a
    never-speculating one stepping at the same grain — the all-reject
    spec window commits exactly one decode_one step, as does a window=1
    plain decode; matched per-step program granularity is what makes a
    bitwise comparison meaningful across XLA programs."""
    pa, pb = _prompt(4, 21), _prompt(5, 22)
    ref_a = _ref(params, pa, 9)
    ref_b = _ref(params, pb, 9)
    wrong_draft, _ = _wrong_draft(ref_a + ref_b)

    def run(speculative):
        engine = _engine(params, num_slots=1, tiered_cache=True,
                         host_tier_entries=4)
        if speculative:
            engine.attach_draft(wrong_draft, _DCFG, version=1)

        toks = {}
        prompts = {"A": pa, "B": pb}

        def ensure(sid):
            """Resident slot for ``sid``: fresh prefill on first touch,
            a tiers promote after (spilling whoever held the slot)."""
            slot = engine.cache.lookup(sid)
            if slot is None:
                slot, _ = engine.cache.acquire(sid)
                if sid not in toks:
                    first, _ = engine.prefill([(slot, True, prompts[sid])])
                    toks[sid] = [int(first[0])]
                else:
                    assert engine.tiers.fill(sid, slot)
            return slot

        def advance(sid, n):
            slot = ensure(sid)
            while n > 0:
                if speculative:
                    win = engine.spec_window([slot], [toks[sid][-1]], [n],
                                             k_draft=2)
                else:
                    win = engine.decode_window([slot], [toks[sid][-1]],
                                               [n], window=1)
                emitted = [int(t) for t in ServeEngine.fetch_window(win)[0]
                           if int(t) != PAD_TOKEN]
                assert len(emitted) == 1  # all-reject: correction only
                toks[sid].extend(emitted)
                n -= len(emitted)

        advance("A", 3)
        advance("B", 3)  # evicts A through the spill worker
        for sid in ("A", "B", "A", "B"):  # promote/evict round trips
            advance(sid, 2)

        def detached(sid):
            ensure(sid)  # promote back if the last switch spilled it
            return engine.detach_session(sid)

        return toks, {sid: detached(sid) for sid in ("A", "B")}

    spec_toks, spec_states = run(speculative=True)
    plain_toks, plain_states = run(speculative=False)
    assert spec_toks == plain_toks
    assert spec_toks["A"] == ref_a[: len(spec_toks["A"])]
    assert spec_toks["B"] == ref_b[: len(spec_toks["B"])]
    for sid in ("A", "B"):
        np.testing.assert_array_equal(np.asarray(spec_states[sid].h),
                                      np.asarray(plain_states[sid].h))
        np.testing.assert_array_equal(np.asarray(spec_states[sid].c),
                                      np.asarray(plain_states[sid].c))


def test_all_reject_kept_sessions_across_tiers_token_identical(params):
    """Scheduler-level tiers leg: kept sessions whose continuations
    promote from the host tier under the REAL batcher serve the same
    tokens with an all-reject draft attached as without one — the
    session cursor survives speculation across spill/promote. (Bitwise
    state equality lives in the matched-granularity test above: the
    plain batcher schedules differently-shaped window programs whose
    fused float math can differ from the spec windows' in final ULPs,
    so cross-program state here is token-exact, not bit-exact.)"""
    pa, pb = _prompt(4, 23), _prompt(5, 24)
    ref_a = _ref(params, pa, 12)
    ref_b = _ref(params, pb, 12)
    wrong_draft, _ = _wrong_draft(ref_a + ref_b)

    def run(speculative):
        engine = _engine(params, num_slots=1, tiered_cache=True,
                         host_tier_entries=4)
        kw = {}
        if speculative:
            engine.attach_draft(wrong_draft, _DCFG, version=1)
            kw = dict(speculative=True, spec_ladder=(2, 4))
        batcher = Batcher(engine, max_active=1, queue_size=8, **kw)
        toks, sids = {}, {}
        # interleaved kept sessions: every continuation promotes its
        # session from the host tier and spills the other
        for name, prompt in (("A", pa), ("B", pb)):
            r = Request(prompt, 6, keep_session=True)
            batcher.submit(r)
            batcher.drain()
            assert r.error is None, r.error
            toks[name] = list(r.tokens)
            sids[name] = r.session_id  # server-assigned kept-session id
        for name in ("A", "B", "A", "B"):
            r = Request([toks[name][-1]], 3, session_id=sids[name],
                        keep_session=True)
            batcher.submit(r)
            batcher.drain()
            assert r.error is None, r.error
            toks[name].extend(r.tokens)
        if speculative:
            assert sum(batcher.spec_windows_dispatched.values()) > 0
            assert batcher.spec_accepted_tokens == 0  # truly all-reject
        return toks

    spec_toks = run(speculative=True)
    plain_toks = run(speculative=False)
    assert spec_toks == plain_toks
    assert spec_toks["A"] == ref_a[: len(spec_toks["A"])]
    assert spec_toks["B"] == ref_b[: len(spec_toks["B"])]


def test_all_reject_prefix_cache_identical(params):
    """The prefix cache is untouched by speculation: the same workload
    (a repeated prompt — second request resumes from the prefix hit)
    leaves identical prefix-cache statistics and identical tokens on a
    speculative all-reject stack and a plain one."""
    p = _prompt(8, 31)
    ref = _ref(params, p, 10)
    wrong_draft, _ = _wrong_draft(ref)

    def run(speculative):
        engine = _engine(params, prefix_cache=True, prefix_stride=4)
        kw = {}
        if speculative:
            engine.attach_draft(wrong_draft, _DCFG, version=1)
            kw = dict(speculative=True, spec_ladder=(2, 4))
        batcher = Batcher(engine, max_active=2, queue_size=8, **kw)
        outs = []
        for _ in range(2):
            r = Request(p, 10)
            batcher.submit(r)
            batcher.drain()
            assert r.error is None
            outs.append(list(r.tokens))
        return outs, engine.prefix.stats()

    spec_outs, spec_prefix = run(speculative=True)
    plain_outs, plain_prefix = run(speculative=False)
    assert spec_outs == plain_outs == [ref, ref]
    assert spec_prefix == plain_prefix
    assert spec_prefix["hits"] >= 1  # the second request actually resumed


# ---- bounded compile lattice + zero-compile knob moves -------------------


def test_spec_compile_lattice_bounded_and_replay_zero(params, draft_params):
    """≤1 compile per ("spec_window", batch-bucket, K_draft) — and a
    replay of the same shapes compiles nothing new."""
    engine = _engine(params)
    engine.attach_draft(draft_params, _DCFG, version=1)
    batcher = Batcher(engine, max_active=4, queue_size=16,
                      speculative=True, spec_ladder=(2, 4))

    def workload(seed):
        reqs = [Request(_prompt(3 + i, seed + i), 12) for i in range(3)]
        for r in reqs:
            batcher.submit(r)
        batcher.drain()
        assert all(r.error is None and len(r.tokens) == 12 for r in reqs)

    workload(40)
    counts = dict(engine.compile_counts)
    assert counts and all(v == 1 for v in counts.values()), counts
    skeys = [k for k in counts if k[0] == "spec_window"]
    assert skeys, counts  # the speculative path actually compiled
    for k in skeys:
        assert k[1] in engine.batch_buckets
        assert k[2] in batcher.spec_ladder and k[2] >= 1
    assert len(skeys) <= (len(engine.batch_buckets)
                          * (len(batcher.spec_ladder) - 1))  # rung 0: none
    workload(60)
    assert dict(engine.compile_counts) == counts


def test_set_spec_k_moves_cost_zero_compiles(params, draft_params):
    """Walking K_draft over the warmed ladder — including rung 0 (plain
    decode) and back up — mid-serving compiles NOTHING: exactly the
    autotuner's guarantee that a knob move never charges a request an
    XLA compile."""
    engine = _engine(params)
    engine.attach_draft(draft_params, _DCFG, version=1)
    server = ServeServer(engine, max_active=4, queue_size=16,
                         speculative=True, spec_ladder=(2, 4))
    with server:
        server.warmup(prompt_lens=(4, 8))
        n0 = engine.num_compiles()
        for k in (0, 2, 4, 2, 0, 4):
            server.batcher.set_spec_k(k)
            req = server.generate(_prompt(4, 50), max_new_tokens=9)
            assert req.error is None, req.error
            assert list(req.tokens) == _ref(params, _prompt(4, 50), 9)
        assert engine.num_compiles() == n0


def test_set_spec_k_validates_ladder_and_mode(params, draft_params):
    engine = _engine(params)
    engine.attach_draft(draft_params, _DCFG, version=1)
    b = Batcher(engine, max_active=2, queue_size=4,
                speculative=True, spec_ladder=(2, 4))
    assert b.spec_ladder == (0, 2, 4)  # rung 0 always present
    assert b.spec_k == 4  # boot default: the top rung
    with pytest.raises(ValueError):
        b.set_spec_k(3)  # not a warmed rung
    plain = Batcher(_engine(params), max_active=2, queue_size=4)
    with pytest.raises(ValueError):
        plain.set_spec_k(2)  # not a speculative scheduler
    with pytest.raises(ValueError):
        # speculative boot without a draft attached
        Batcher(_engine(params), max_active=2, queue_size=4,
                speculative=True)


# ---- the autotuner's spec_k law ------------------------------------------


def _sig(*, itl=(0, None), qwait=(0, None), ttft=(0, None), queued=0,
         queue_size=8, chunks=0.0, tiers=None, spec_accept=None):
    def h(pair):
        count, p99 = pair
        out = {"count": count, "sum": 0.0}
        if p99 is not None:
            out["p50"] = p99 / 2
            out["p99"] = p99
        return out

    return {"ttft": h(ttft), "itl": h(itl), "queue_wait": h(qwait),
            "queued": queued, "queue_size": queue_size,
            "prefill_chunks": chunks, "tiers": tiers,
            "spec_accept": spec_accept}


def _accept(count, mean):
    return {"count": count, "sum": count * mean}


def _spec_server(params, draft_params):
    engine = _engine(params)
    engine.attach_draft(draft_params, _DCFG, version=1)
    return ServeServer(engine, max_active=4, queue_size=8,
                       window_ladder=(1, 2, 4),
                       speculative=True, spec_ladder=(2, 4))


def _tuner(server, **cfg_kw):
    cfg_kw.setdefault("slo_s", 0.2)
    cfg_kw.setdefault("min_events", 4)
    cfg_kw.setdefault("patience_up", 2)
    cfg_kw.setdefault("patience_down", 1)
    cfg_kw.setdefault("cooldown", 0)
    return AutoTuner(server, AutoTuneConfig(**cfg_kw))


def _spec_moves(moves):
    return [(m["knob"], m["direction"]) for m in moves
            if m["knob"] == "spec_k"]


def test_tuner_spec_k_up_on_saturating_acceptance(params, draft_params):
    server = _spec_server(params, draft_params)
    server.batcher.set_spec_k(2)  # mid-ladder operating point
    tuner = _tuner(server)
    sat = _sig(spec_accept=_accept(8, 1.8))  # mean 1.8 >= 0.8 * 2
    assert _spec_moves(tuner.tick(sat)) == []  # patience_up = 2
    assert _spec_moves(tuner.tick(sat)) == [("spec_k", "up")]
    assert server.batcher.spec_k == 4
    for _ in range(4):  # at the top rung: no overshoot
        tuner.tick(_sig(spec_accept=_accept(8, 3.6)))
    assert server.batcher.spec_k == 4


def test_tuner_spec_k_down_fast_and_rung0_is_plain_decode(params,
                                                          draft_params):
    server = _spec_server(params, draft_params)
    tuner = _tuner(server)
    assert server.batcher.spec_k == 4
    waste = _sig(spec_accept=_accept(8, 0.4))  # mean < 0.5 * K: fast down
    assert _spec_moves(tuner.tick(waste)) == [("spec_k", "down")]
    assert server.batcher.spec_k == 2
    assert _spec_moves(tuner.tick(waste)) == [("spec_k", "down")]
    assert server.batcher.spec_k == 0  # the K=0 fallback: plain decode
    # at rung 0 there is NO acceptance evidence — stale acceptance
    # deltas must not move the knob; only live decode traffic re-probes
    assert _spec_moves(tuner.tick(waste)) == []
    assert server.batcher.spec_k == 0


def test_tuner_spec_k_rung0_reprobes_on_decode_traffic(params,
                                                       draft_params):
    server = _spec_server(params, draft_params)
    server.batcher.set_spec_k(0)
    tuner = _tuner(server)
    quiet = _sig()  # no traffic: stay parked at plain decode
    for _ in range(3):
        assert _spec_moves(tuner.tick(quiet)) == []
    assert server.batcher.spec_k == 0
    busy = _sig(itl=(20, 0.002))  # live decode traffic: re-probe
    assert _spec_moves(tuner.tick(busy)) == []  # patience_up = 2
    assert _spec_moves(tuner.tick(busy)) == [("spec_k", "up")]
    assert server.batcher.spec_k == 2


def test_tuner_spec_k_inert_on_nonspeculative_stack(params):
    server = ServeServer(_engine(params), max_active=4, queue_size=8)
    tuner = _tuner(server)
    for _ in range(3):
        assert _spec_moves(tuner.tick(
            _sig(itl=(20, 0.002), spec_accept=_accept(8, 3.0)))) == []
    assert tuner.stats()["knobs"]["spec_k"] == {"value": None, "ladder": []}
