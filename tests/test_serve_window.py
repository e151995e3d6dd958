"""Windowed multi-token decode (serve/engine.py `decode_window` +
serve/batcher.py adaptive windowing with async readback).

The contract under test:

- greedy output through the windowed path is TOKEN-IDENTICAL to the K=1
  path and to `models/generate.py`, across window boundaries and when EOS
  lands inside a window;
- the compile lattice stays bounded: at most ONE XLA compile per
  ("decode_window", batch-bucket, K, sampling-config), proved by replay;
- dispatch-ahead pipelining (window i+1 dispatched from window i's device
  handles before window i is fetched) changes nothing observable;
- a request submitted while a window is in flight is admitted within one
  scheduler iteration (the continuous-batching admission property).
"""

import threading

import jax
import numpy as np
import pytest

from lstm_tensorspark_tpu.models import LMConfig, init_lm, make_generate_fn
from lstm_tensorspark_tpu.serve import (
    PAD_TOKEN,
    Batcher,
    Request,
    ServeEngine,
    ServeServer,
    InprocessClient,
)

_CFG = LMConfig(vocab_size=37, hidden_size=16, num_layers=2)


def _params():
    return init_lm(jax.random.PRNGKey(11), _CFG)


def _engine(params, **kw):
    kw.setdefault("num_slots", 8)
    kw.setdefault("prefill_buckets", (4, 8))
    kw.setdefault("batch_buckets", (1, 2, 4))
    return ServeEngine(params, _CFG, **kw)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 37, size=n).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def windowed(params):
    """One module-scoped windowed server (ladder 1/4/8 — the default)."""
    server = ServeServer(_engine(params), max_active=4, queue_size=16)
    server.start()
    yield server
    server.stop()


# ---- greedy parity across window boundaries ------------------------------


def test_windowed_greedy_matches_k1_and_generate(params, windowed):
    """max_new_tokens values straddling the ladder (10 = prefill+8+1,
    13 = prefill+8+4 — both cross window boundaries mid-stream) must be
    token-identical to the per-token batcher AND to models/generate.py."""
    prompts = [_prompt(3, 1), _prompt(6, 2)]
    k1 = ServeServer(_engine(params), max_active=4, queue_size=16,
                     window_ladder=(1,))
    client_w = InprocessClient(windowed)
    with k1:
        client_1 = InprocessClient(k1)
        for n_new in (10, 13):
            gen = make_generate_fn(_CFG, max_new_tokens=n_new, greedy=True)
            for p in prompts:
                ref = np.asarray(
                    gen(params, p[None, :], jax.random.PRNGKey(0))
                )[0, p.size:]
                got_w = client_w.generate(p, max_new_tokens=n_new)
                got_1 = client_1.generate(p, max_new_tokens=n_new)
                np.testing.assert_array_equal(np.asarray(got_w), ref)
                np.testing.assert_array_equal(np.asarray(got_1), ref)
    # the windowed server actually used windows (not a silent K=1 run)
    dispatched = windowed.batcher.windows_dispatched
    assert any(k > 1 for k in dispatched), dispatched


def test_concurrent_windowed_sessions_match_generate(params, windowed):
    prompts = [_prompt(2, 3), _prompt(7, 5)]
    n_new = 11
    client = InprocessClient(windowed)
    got = [None] * len(prompts)

    def run_one(i):
        got[i] = client.generate(prompts[i], max_new_tokens=n_new)

    threads = [threading.Thread(target=run_one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    gen = make_generate_fn(_CFG, max_new_tokens=n_new, greedy=True)
    for i, p in enumerate(prompts):
        ref = np.asarray(gen(params, p[None, :], jax.random.PRNGKey(0)))[
            0, p.size:]
        np.testing.assert_array_equal(np.asarray(got[i], np.int32), ref)


# ---- EOS inside a window -------------------------------------------------


def test_eos_inside_window_stops_exactly(params, windowed):
    """Pick an EOS id that the greedy stream emits mid-window: the
    windowed request must stop AT that token (on-device latch → PAD
    padding afterwards), exactly like the K=1 path."""
    p = _prompt(4, 6)
    client = InprocessClient(windowed)
    probe = client.generate(p, max_new_tokens=12)
    assert len(probe) == 12
    # an id first emitted strictly inside the first K=8 window
    eos, first_idx = None, None
    for idx in range(2, 7):
        if probe[idx] not in probe[:idx]:
            eos, first_idx = probe[idx], idx
            break
    if eos is None:
        pytest.skip("greedy stream has no unique mid-window token")
    again = client.generate(p, max_new_tokens=12, eos_id=int(eos))
    # stops AT the eos token — identical to truncating the eos-free
    # stream there, which is exactly what the K=1 path does (greedy
    # windowed/K=1 parity itself is test_windowed_greedy_matches_*)
    assert again == probe[: first_idx + 1]


def test_window_program_pads_after_eos(params):
    """Engine-level: the rows of a fetched window are PAD_TOKEN after the
    EOS position, and a pipelined follow-up window (dispatched BEFORE the
    fetch) leaves the latched row frozen."""
    engine = _engine(params)
    slot, _ = engine.cache.acquire("s")
    first, _ = engine.prefill([(slot, True, _prompt(3, 7))])
    # probe the continuation to find a mid-window token to use as EOS
    probe_win = engine.decode_window([slot], [int(first[0])], [8], window=8)
    stream = [int(t) for t in ServeEngine.fetch_window(probe_win)[0]]
    eos = stream[2]
    first_idx = stream.index(eos)

    # fresh session, same engine (the compiled programs replay): rerun
    # the same continuation WITH the eos armed
    slot2, _ = engine.cache.acquire("s2")
    f2, _ = engine.prefill([(slot2, True, _prompt(3, 7))])
    win = engine.decode_window([slot2], [int(f2[0])], [8],
                               eos_ids=[eos], window=8)
    nxt = engine.decode_window_next(win)  # dispatch-ahead, pre-fetch
    row = ServeEngine.fetch_window(win)[0]
    assert [int(t) for t in row[: first_idx + 1]] == stream[: first_idx + 1]
    assert all(int(t) == PAD_TOKEN for t in row[first_idx + 1:])
    # the latched row stays frozen through the pipelined window: all PAD
    assert all(int(t) == PAD_TOKEN for t in ServeEngine.fetch_window(nxt)[0])


# ---- bounded compile lattice ---------------------------------------------


def test_window_compile_lattice_bounded(params):
    """≤1 compile per ("decode_window", batch-bucket, K, sampling) —
    asserted via trace-time compile_counts, then re-proved by replaying
    the same workload shape (zero new compiles). Driven through the
    Batcher directly (submit-then-drain) so admission batching — and
    therefore the program shapes — is deterministic, unlike racing
    client threads."""
    engine = _engine(params)
    batcher = Batcher(engine, max_active=4, queue_size=16)

    def workload(seed):
        reqs = [Request(_prompt(3 + i, seed + i), 12) for i in range(3)]
        for r in reqs:
            batcher.submit(r)
        batcher.drain()
        assert all(r.error is None and len(r.tokens) == 12 for r in reqs)

    workload(20)
    counts = dict(engine.compile_counts)
    assert counts and all(v == 1 for v in counts.values()), counts
    wkeys = [k for k in counts if k[0] == "decode_window"]
    assert wkeys, counts  # the windowed path actually compiled windows
    for k in wkeys:
        assert k[1] in engine.batch_buckets  # batch bucket
        assert k[2] in batcher.window_ladder  # K is a ladder rung
    # ladder lattice bound: |batch buckets| x |ladder|
    assert len(wkeys) <= (len(engine.batch_buckets)
                          * len(batcher.window_ladder))
    workload(50)  # same shapes again → zero new compiles
    assert dict(engine.compile_counts) == counts


def test_warmup_precompiles_window_lattice(params):
    engine = _engine(params, batch_buckets=(1, 2))
    n = engine.warmup(prompt_lens=(3,), windows=(1, 8))
    counts = dict(engine.compile_counts)
    assert all(v == 1 for v in counts.values())
    # every rung gets a window program (K=1 included: the pipelined tail
    # dispatches K=1 windows)
    assert engine.num_compiles("decode_window") == 2 * 2  # buckets x ladder
    assert engine.warmup(prompt_lens=(3,), windows=(1, 8)) == n
    assert dict(engine.compile_counts) == counts


# ---- admission latency under windowing -----------------------------------


def test_mid_window_submit_admitted_within_one_iteration(params):
    """A request submitted while a decode window is in flight must be
    admitted (prefilled, first token produced) by the NEXT scheduler
    iteration — the continuous-batching admission property survives
    windowing because the window ladder drops to K=1 while the queue is
    non-empty."""
    engine = _engine(params)
    batcher = Batcher(engine, max_active=4, queue_size=8)
    long_req = Request(_prompt(4, 30), 24)
    batcher.submit(long_req)
    batcher.step()  # admit + dispatch the first window
    assert batcher._pending is not None  # a window IS in flight
    late = Request(_prompt(2, 31), 2)
    batcher.submit(late)
    batcher.step()  # ONE iteration: resolve the window AND admit `late`
    assert late.t_first_token is not None and len(late.tokens) >= 1
    batcher.drain()
    assert late.error is None and long_req.error is None
    assert len(long_req.tokens) == 24
    # while the queue was non-empty / rows mixed, ladder fell back — but
    # steady-state did pipeline at least one window ahead
    assert batcher.windows_pipelined >= 1
    assert engine.cache.stats()["live_sessions"] == 0


def test_cache_generation_counts_window_grain(params):
    """The cache advances once per PROGRAM (window), not per token:
    tokens_generated / generation grows with the window size."""
    engine = _engine(params)
    batcher = Batcher(engine, max_active=2, queue_size=4)
    req = Request(_prompt(3, 40), 17)
    batcher.submit(req)
    batcher.drain()
    gen = engine.cache.stats()["generation"]
    assert len(req.tokens) == 17
    # 1 prefill + windows(8+8+... / ladder tail) — far fewer programs
    # than 1 + 16 per-token decodes
    assert gen < 1 + 16, gen
