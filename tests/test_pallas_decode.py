"""Fused Pallas decode-window kernel (ops/pallas_decode.py +
serve/engine.py ``decode_kernel="pallas"``), CPU interpreter mode.

The contract under test:

- greedy AND temperature-sampled decode through the Pallas window is
  TOKEN-IDENTICAL to the `lax.scan` window and to `models/generate.py`,
  across batch buckets, the K ladder, EOS-in-window and budget-latch
  edges (off-TPU the kernel runs interpreted — same kernel body, same
  tokens; `tests_tpu/test_pallas_decode_tpu.py` is the compiled gate);
- the compile lattice stays bounded: ≤1 trace per
  ``("decode_window_pallas", bucket, K, sampling)``, covered by warmup;
- sampling configs the kernel cannot reproduce bit-exactly (top-k /
  top-p need an in-kernel sort) fall back to the scan window, counted;
- the window readback contract is kernel-independent: PAD_TOKEN rows,
  ``fetch_window``/``fetch_window_summary`` and the request phase
  timeline behave identically for both kernels (the regression pin for
  the readback/phase-timeline path).
"""

import threading

import jax
import numpy as np
import pytest

from lstm_tensorspark_tpu.models import LMConfig, init_lm, make_generate_fn
from lstm_tensorspark_tpu.ops import pallas_decode
from lstm_tensorspark_tpu.serve import (
    PAD_TOKEN,
    Batcher,
    Request,
    ServeEngine,
    ServeServer,
    InprocessClient,
)
from lstm_tensorspark_tpu.serve.engine import GREEDY, SamplingParams

_CFG = LMConfig(vocab_size=37, hidden_size=16, num_layers=2)


@pytest.fixture(scope="module")
def params():
    return init_lm(jax.random.PRNGKey(11), _CFG)


def _engine(params, kernel="pallas", **kw):
    kw.setdefault("num_slots", 8)
    kw.setdefault("prefill_buckets", (4, 8))
    kw.setdefault("batch_buckets", (1, 2, 4))
    return ServeEngine(params, _CFG, decode_kernel=kernel, **kw)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 37, size=n).astype(np.int32)


def _window_stream(engine, prompt, sampling, *, budget, window, eos_id=None):
    """prefill + decode_window chain through the engine's public path;
    returns (tokens incl. the prefill token, last summary)."""
    sid = f"s{engine.decode_kernel}{np.random.randint(1 << 30)}"
    slot, _ = engine.cache.acquire(sid)
    first, _ = engine.prefill([(slot, True, prompt)], sampling)
    out = [int(first[0])]
    remaining = budget
    last = int(first[0])
    summary = None
    while remaining > 0:
        win = engine.decode_window(
            [slot], [last], [remaining],
            eos_ids=None if eos_id is None else [eos_id],
            sampling=sampling, window=window)
        toks, rem, alive, _ = engine.fetch_window_summary(win)
        summary = (rem.copy(), alive.copy())
        emitted = [int(t) for t in toks[0] if t != PAD_TOKEN]
        out.extend(emitted)
        remaining -= len(emitted)
        if not alive[0]:
            break
        last = out[-1]
    engine.cache.release(sid)
    return out, summary


# ---- engine resolution ---------------------------------------------------


def test_kernel_resolution_and_auto(params):
    assert _engine(params, "pallas").decode_kernel == "pallas"
    assert _engine(params, "scan").decode_kernel == "scan"
    # auto stays on scan off-TPU: interpreted pallas is a correctness
    # path, not a fast one
    auto = _engine(params, "auto")
    if jax.default_backend() != "tpu":
        assert auto.decode_kernel == "scan"
    with pytest.raises(ValueError):
        _engine(params, "mosaic")


# ---- token parity: pallas vs scan vs models/generate ---------------------


@pytest.mark.parametrize("window", [1, 4, 8])
def test_greedy_parity_across_k_ladder(params, window):
    ep = _engine(params)
    es = _engine(params, "scan")
    for seed, plen, budget in ((1, 3, 10), (2, 6, 13), (3, 8, 5)):
        p = _prompt(plen, seed)
        got_p, _ = _window_stream(ep, p, GREEDY, budget=budget,
                                  window=window)
        got_s, _ = _window_stream(es, p, GREEDY, budget=budget,
                                  window=window)
        gen = make_generate_fn(_CFG, max_new_tokens=budget + 1, greedy=True)
        ref = np.asarray(gen(params, p[None, :], jax.random.PRNGKey(0)))[
            0, p.size:]
        assert got_p == got_s == list(ref)
    # the pallas engine really compiled pallas window programs
    assert any(k[0] == "decode_window_pallas" for k in ep.compile_counts)
    assert not any(k[0] == "decode_window_pallas" for k in es.compile_counts)


def test_greedy_parity_across_batch_buckets(params):
    """Packed multi-row windows (bucket 2 and 4, with padding rows) —
    every row token-identical to the scan window."""
    for kernel in ("pallas", "scan"):
        e = _engine(params, kernel)
        slots = []
        prompts = [_prompt(3, 21), _prompt(5, 22), _prompt(4, 23)]
        for i, p in enumerate(prompts):
            slot, _ = e.cache.acquire(f"b{i}")
            slots.append(slot)
        first, _ = e.prefill([(s, True, p) for s, p in zip(slots, prompts)])
        win = e.decode_window(slots, [int(t) for t in first],
                              [6] * 3, window=8)
        toks = e.fetch_window(win)
        if kernel == "pallas":
            got_pallas = toks.tolist()
        else:
            assert toks.tolist() == got_pallas


def test_sampled_parity_temperature(params):
    """Temperature sampling through the Pallas kernel is bit-identical
    to the scan window: same engine rng chain, same Gumbel draws, same
    argmax — token for token."""
    samp = SamplingParams(temperature=0.7)
    ep = _engine(params, rng_seed=9)
    es = _engine(params, "scan", rng_seed=9)
    p = _prompt(5, 31)
    got_p, _ = _window_stream(ep, p, samp, budget=12, window=4)
    got_s, _ = _window_stream(es, p, samp, budget=12, window=4)
    assert got_p == got_s
    assert len(got_p) == 13
    # a second stream continues both rng chains in lockstep
    got_p2, _ = _window_stream(ep, p, samp, budget=8, window=8)
    got_s2, _ = _window_stream(es, p, samp, budget=8, window=8)
    assert got_p2 == got_s2


# ---- EOS / budget latch edges --------------------------------------------


def test_eos_latch_inside_window(params):
    ep = _engine(params)
    p = _prompt(4, 6)
    probe, _ = _window_stream(ep, p, GREEDY, budget=12, window=8)
    stream = probe[1:]  # post-prefill continuation
    eos, first_idx = None, None
    for idx in range(1, 6):
        if stream[idx] not in stream[:idx]:
            eos, first_idx = stream[idx], idx
            break
    if eos is None:
        pytest.skip("greedy stream has no unique mid-window token")
    es = _engine(params, "scan")
    got_p, sum_p = _window_stream(ep, p, GREEDY, budget=12, window=8,
                                  eos_id=int(eos))
    got_s, sum_s = _window_stream(es, p, GREEDY, budget=12, window=8,
                                  eos_id=int(eos))
    assert got_p == got_s == probe[: first_idx + 2]
    # the on-device summary latched the row dead in both kernels
    assert not sum_p[1][0] and not sum_s[1][0]


@pytest.mark.parametrize("budget", [1, 3, 7, 8])
def test_budget_latch_edges(params, budget):
    """Budgets straddling the window size: the row latches dead ON
    DEVICE exactly at the budget, PAD after, summary remaining == 0."""
    ep = _engine(params)
    es = _engine(params, "scan")
    p = _prompt(5, 40)
    for e in (ep, es):
        slot, _ = e.cache.acquire("s")
        first, _ = e.prefill([(slot, True, p)])
        win = e.decode_window([slot], [int(first[0])], [budget], window=8)
        toks, rem, alive, _ = e.fetch_window_summary(win)
        row = [int(t) for t in toks[0]]
        assert all(t != PAD_TOKEN for t in row[:budget])
        assert all(t == PAD_TOKEN for t in row[budget:])
        assert rem[0] == 0 and not alive[0]
        e.cache.release("s")


def test_pipelined_followup_window_stays_frozen(params):
    """decode_window_next from an EOS-latched pallas window (dispatch-
    ahead, pre-fetch): the latched row stays frozen — all PAD."""
    e = _engine(params)
    slot, _ = e.cache.acquire("s")
    first, _ = e.prefill([(slot, True, _prompt(3, 7))])
    probe = e.decode_window([slot], [int(first[0])], [8], window=8)
    stream = [int(t) for t in ServeEngine.fetch_window(probe)[0]]
    eos = stream[2]
    slot2, _ = e.cache.acquire("s2")
    f2, _ = e.prefill([(slot2, True, _prompt(3, 7))])
    win = e.decode_window([slot2], [int(f2[0])], [8], eos_ids=[eos],
                          window=8)
    nxt = e.decode_window_next(win)  # dispatch-ahead, pre-fetch
    first_idx = stream.index(eos)
    row = ServeEngine.fetch_window(win)[0]
    assert [int(t) for t in row[: first_idx + 1]] == stream[: first_idx + 1]
    assert all(int(t) == PAD_TOKEN for t in row[first_idx + 1:])
    assert all(int(t) == PAD_TOKEN for t in ServeEngine.fetch_window(nxt)[0])


# ---- warmup coverage + bounded lattice -----------------------------------


def test_warmup_covers_pallas_lattice_and_replay(params):
    e = _engine(params, batch_buckets=(1, 2))
    n = e.warmup(prompt_lens=(3,), windows=(1, 8))
    counts = dict(e.compile_counts)
    assert all(v == 1 for v in counts.values())
    pkeys = [k for k in counts if k[0] == "decode_window_pallas"]
    assert len(pkeys) == 2 * 2  # buckets x ladder — all pallas, no scan
    assert not any(k[0] == "decode_window" for k in counts)
    assert e.warmup(prompt_lens=(3,), windows=(1, 8)) == n
    assert dict(e.compile_counts) == counts


def test_server_end_to_end_pallas_matches_generate(params):
    """Full server path (batcher ladder, pipelining, readback) on the
    pallas kernel: concurrent sessions token-identical to generate()."""
    server = ServeServer(_engine(params), max_active=4, queue_size=16)
    prompts = [_prompt(2, 3), _prompt(7, 5)]
    n_new = 11
    got = [None] * len(prompts)
    with server:
        client = InprocessClient(server)

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
    assert any(k > 1 for k in server.batcher.windows_dispatched)
    assert any(k[0] == "decode_window_pallas"
               for k in server.engine.compile_counts)


# ---- unsupported-sampling fallback ---------------------------------------


def test_topk_topp_fall_back_to_scan_window(params):
    samp = SamplingParams(temperature=1.0, top_k=5)
    ep = _engine(params, rng_seed=4)
    es = _engine(params, "scan", rng_seed=4)
    p = _prompt(5, 50)
    got_p, _ = _window_stream(ep, p, samp, budget=8, window=4)
    got_s, _ = _window_stream(es, p, samp, budget=8, window=4)
    assert got_p == got_s  # the fallback IS the scan window
    assert ep.decode_window_scan_fallbacks > 0
    assert ep.stats()["decode_window_scan_fallbacks"] > 0
    assert not any(k[0] == "decode_window_pallas" for k in ep.compile_counts)
    assert not pallas_decode.sampling_supported(1.0, 5, None, False)
    assert not pallas_decode.sampling_supported(1.0, None, 0.9, False)
    assert pallas_decode.sampling_supported(0.5, None, None, False)


def test_vmem_plan_gate(params):
    """A shape whose working set cannot fit VMEM refuses the kernel (the
    engine would fall back); a tiny one fits."""
    assert pallas_decode.plan_fits(2, 8, 2, 16, 16, 37, sampled=True)
    assert not pallas_decode.plan_fits(16, 8, 2, 1024, 1024, 65536,
                                      sampled=True)


# ---- the window readback contract, pinned for BOTH kernels ---------------


@pytest.mark.parametrize("kernel", ["pallas", "scan"])
def test_window_readback_contract_both_kernels(params, kernel):
    """Regression pin (the fetch_window PAD_TOKEN round-trip): whatever
    kernel produced the window, (a) fetch_window returns PAD-padded rows
    that stop the host walk, (b) fetch_window_summary agrees with the
    PAD structure, and (c) the request phase timeline still records the
    decode_window + readback spans — the phase-timeline path must not
    care which kernel filled the handles."""
    e = _engine(params, kernel)
    server = ServeServer(e, max_active=2, queue_size=8)
    with server:
        client = InprocessClient(server)
        probe = client.generate(_prompt(4, 6), max_new_tokens=12)
        eos = None
        for idx in range(2, 7):
            if probe[idx] not in probe[:idx]:
                eos, first_idx = probe[idx], idx
                break
        if eos is None:
            pytest.skip("greedy stream has no unique mid-window token")
        req = server.generate(_prompt(4, 6), max_new_tokens=12,
                              eos_id=int(eos))
    # EOS stops the stream exactly where the eos-free stream first
    # emitted that token — the PAD tail never leaked into the output
    assert list(req.tokens) == probe[: first_idx + 1]
    assert PAD_TOKEN not in req.tokens
    phases = [name for name, _, _ in req.phases]
    assert "decode_window" in phases and "readback" in phases
    # engine-level: the raw window rows carry PAD after the latch and
    # the summary matches, for this kernel
    slot, _ = e.cache.acquire("pin")
    first, _ = e.prefill([(slot, True, _prompt(4, 6))])
    win = e.decode_window([slot], [int(first[0])], [12],
                          eos_ids=[int(eos)], window=8)
    row = ServeEngine.fetch_window(win)[0]
    toks, rem, alive, _ = e.fetch_window_summary(win)
    np.testing.assert_array_equal(row, toks[0])
    pad_idx = [i for i, t in enumerate(row) if t == PAD_TOKEN]
    if pad_idx:  # eos landed inside this window
        assert not alive[0]
        assert all(int(t) == PAD_TOKEN for t in row[pad_idx[0]:])
    e.cache.release("pin")
