"""Batcher/scheduler tests (serve/batcher.py + serve/engine.py): bucket
padding, the bounded-recompile contract (at most ONE XLA compile per
(phase, bucket) even under mixed prompt lengths), backpressure, and
continuous-batching fairness.

Most tests share one module-scoped engine (each builds its own Batcher —
batchers are free) so the file pays each (phase, bucket) compile once;
the shared-engine compile-count assertions stay valid precisely BECAUSE
of the contract under test: replaying a shape never recompiles it. Tests
that assert exact fresh-engine counts build their own small engine."""

import jax
import numpy as np
import pytest

from lstm_tensorspark_tpu.models import LMConfig, init_lm
from lstm_tensorspark_tpu.serve import (
    Batcher,
    QueueFullError,
    Request,
    SamplingParams,
    ServeEngine,
)

_CFG = LMConfig(vocab_size=29, hidden_size=12, num_layers=1)


def _make_engine(**kw):
    params = init_lm(jax.random.PRNGKey(1), _CFG)
    kw.setdefault("num_slots", 16)
    kw.setdefault("prefill_buckets", (4, 8, 16))
    kw.setdefault("batch_buckets", (1, 2, 4))
    return ServeEngine(params, _CFG, **kw)


@pytest.fixture(scope="module")
def engine():
    return _make_engine()


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 29, size=n).astype(np.int32)


# ---- bucket padding ------------------------------------------------------


def test_prefill_pads_to_length_bucket(engine):
    # runs FIRST in the file (tests are order-stable: no pytest-randomly
    # in tier-1), so the engine's compile log is still empty
    scratch = engine.cache.scratch_slot
    engine.prefill([(scratch, True, _prompt(3))])   # 3 → bucket 4
    engine.prefill([(scratch, True, _prompt(11))])  # 11 → bucket 16
    keys = set(engine.compile_counts)
    assert ("prefill", 1, 4, SamplingParams(greedy=True).key()) in keys
    assert ("prefill", 1, 16, SamplingParams(greedy=True).key()) in keys
    # no compile for the skipped middle bucket
    assert not any(k[0] == "prefill" and k[2] == 8 for k in keys)


def test_batch_pads_to_batch_bucket(engine):
    scratch = engine.cache.scratch_slot
    items = [(scratch, True, _prompt(2, s)) for s in range(3)]
    out, _ = engine.prefill(items)  # 3 rows → batch bucket 4
    assert out.shape == (3,)  # padding rows are stripped from the result
    assert any(k[0] == "prefill" and k[1] == 4 for k in engine.compile_counts)
    nxt, _ = engine.decode([scratch] * 3, [1, 2, 3])
    assert nxt.shape == (3,)
    assert any(k[0] == "decode" and k[1] == 4 for k in engine.compile_counts)


def test_prompt_longer_than_largest_bucket_rejected(engine):
    batcher = Batcher(engine, max_active=4, queue_size=4)
    with pytest.raises(ValueError):
        batcher.submit(Request(_prompt(17), 2))  # > max bucket 16


# ---- bounded recompiles --------------------------------------------------


def test_one_compile_per_bucket_and_phase_under_mixed_lengths(engine):
    """The ISSUE acceptance bound: a run with mixed prompt lengths triggers
    at most one XLA compile per (bucket, phase) — asserted via trace-time
    counters, then re-proved by replaying the same workload shape."""
    batcher = Batcher(engine, max_active=4, queue_size=32)
    lengths = [2, 3, 4, 5, 7, 8, 9, 13, 16, 1, 6, 11]
    for i, t in enumerate(lengths):
        batcher.submit(Request(_prompt(t, seed=i), 3))
    batcher.drain()

    counts = dict(engine.compile_counts)
    assert counts, "no compiles recorded"
    assert all(v == 1 for v in counts.values()), counts
    # phases compile per-bucket, not per-request: far fewer programs than
    # requests
    assert engine.num_compiles("prefill") <= 3 * 3  # |len buckets| x |batch|
    assert engine.num_compiles("decode") <= 3       # |batch buckets|

    before = dict(counts)
    for i, t in enumerate(lengths):  # same shapes again → zero new compiles
        batcher.submit(Request(_prompt(t, seed=100 + i), 3))
    batcher.drain()
    assert dict(engine.compile_counts) == before


def test_warmup_precompiles_the_lattice():
    own = _make_engine(prefill_buckets=(4,), batch_buckets=(1, 2))
    n_programs = own.warmup(prompt_lens=(3,))
    counts = dict(own.compile_counts)
    assert all(v == 1 for v in counts.values())
    # every batch bucket compiled for decode and for the length bucket
    assert own.num_compiles("decode") == 2
    assert own.num_compiles("prefill") == 2
    # replay: warmup again → nothing new
    assert own.warmup(prompt_lens=(3,)) == n_programs
    assert dict(own.compile_counts) == counts


# ---- backpressure / admission control -----------------------------------


def test_bounded_queue_backpressure(engine):
    batcher = Batcher(engine, max_active=2, queue_size=2)
    batcher.submit(Request(_prompt(2), 2))
    batcher.submit(Request(_prompt(2), 2))
    with pytest.raises(QueueFullError):
        batcher.submit(Request(_prompt(2), 2))
    assert batcher.rejected == 1
    batcher.drain()  # queue drains; admission resumes
    batcher.submit(Request(_prompt(2), 2))
    batcher.drain()
    assert batcher.completed == 3


def test_max_active_bounds_admission(engine):
    batcher = Batcher(engine, max_active=2, queue_size=16)
    reqs = [Request(_prompt(2, s), 6) for s in range(5)]
    for r in reqs:
        batcher.submit(r)
    batcher.step()
    stats = batcher.stats()
    assert stats["active"] == 2 and stats["queued"] == 3
    batcher.drain()
    assert batcher.completed == 5


def test_max_active_cannot_exceed_cache_slots():
    own = _make_engine(num_slots=2)
    with pytest.raises(ValueError):
        Batcher(own, max_active=3)


# ---- fairness / continuous batching -------------------------------------


def test_every_active_session_advances_each_step(engine):
    # window_ladder=(1,) pins the per-token path: this test asserts the
    # EXACT one-token-per-step cadence (the windowed cadence — up to K
    # tokens per iteration, delivered a step later — is covered by
    # tests/test_serve_window.py)
    batcher = Batcher(engine, max_active=4, queue_size=8, window_ladder=(1,))
    a = Request(_prompt(2, 0), 6)
    b = Request(_prompt(3, 1), 6)
    batcher.submit(a)
    batcher.submit(b)
    batcher.step()  # admission+prefill gives each its first token, then +1
    assert len(a.tokens) == len(b.tokens) == 2
    batcher.step()
    assert len(a.tokens) == len(b.tokens) == 3
    batcher.drain()


def test_late_short_request_finishes_before_early_long_one(engine):
    """The continuous-batching property: prefills join between decode
    steps, so a short request submitted late completes while an earlier
    long session is still decoding."""
    batcher = Batcher(engine, max_active=4, queue_size=8)
    long_req = Request(_prompt(4, 0), 12)
    batcher.submit(long_req)
    batcher.step()
    batcher.step()  # long session mid-flight
    short = Request(_prompt(2, 1), 2)
    batcher.submit(short)
    steps = 0
    while not short.done.is_set() and steps < 10:
        batcher.step()
        steps += 1
    assert short.done.is_set() and short.error is None
    assert not long_req.done.is_set()  # still decoding
    batcher.drain()
    assert long_req.done.is_set() and len(long_req.tokens) == 12


def test_eos_stops_early(engine):
    batcher = Batcher(engine, max_active=2, queue_size=4)
    probe = Request(_prompt(3, 2), 6)
    batcher.submit(probe)
    batcher.drain()
    eos = probe.tokens[2]
    again = Request(_prompt(3, 2), 6, eos_id=eos)
    batcher.submit(again)
    batcher.drain()
    # stops AT the first eos token, wherever the probe first emitted it
    assert again.tokens == probe.tokens[:probe.tokens.index(eos) + 1]


def test_sampling_config_cap_bounds_compiles():
    """Sampling params are compile keys and client-controlled at the HTTP
    boundary: the engine refuses configs beyond max_sampling_configs
    instead of compile-thrashing."""
    own = _make_engine(max_sampling_configs=1, prefill_buckets=(4,),
                       batch_buckets=(1,))
    scratch = own.cache.scratch_slot
    own.prefill([(scratch, True, _prompt(2))])  # greedy takes the one slot
    with pytest.raises(ValueError, match="sampling configs"):
        own.decode([scratch], [0], SamplingParams(temperature=0.5))
    # the refusal happens before any trace: nothing new compiled
    assert own.num_compiles() == 1


def test_mixed_sampling_configs_batch_separately(engine):
    batcher = Batcher(engine, max_active=4, queue_size=8)
    greedy = Request(_prompt(2, 3), 3)
    sampled = Request(_prompt(2, 4), 3,
                      sampling=SamplingParams(temperature=0.7, top_k=5))
    batcher.submit(greedy)
    batcher.submit(sampled)
    batcher.drain()
    assert greedy.error is None and sampled.error is None
    assert len(greedy.tokens) == len(sampled.tokens) == 3
    skeys = {k[-1] for k in engine.compile_counts}
    assert len(skeys) == 2  # two sampling configs → two program families


def test_concurrent_requests_on_one_session_rejected(engine):
    """Two in-flight requests on one session_id would share a cache slot
    and corrupt each other's carries — the newcomer must fail loudly."""
    batcher = Batcher(engine, max_active=4, queue_size=8)
    first = Request(_prompt(2, 0), 6, keep_session=True)
    batcher.submit(first)
    batcher.step()  # first is now active
    # first's sid is assigned at admission; read it off the active session
    dup = Request(_prompt(2, 1), 2, session_id=batcher._active[0].sid)
    batcher.submit(dup)
    batcher.drain()
    assert dup.error is not None and "busy" in dup.error
    assert first.error is None and len(first.tokens) == 6
    engine.cache.release(first.session_id)


def test_cancelled_requests_dropped_and_freed(engine):
    """A client that times out sets .cancelled: queued requests drop at
    admission, active ones retire mid-decode and free their slot."""
    batcher = Batcher(engine, max_active=2, queue_size=8)
    active_req = Request(_prompt(2, 0), 8)
    queued_req = Request(_prompt(2, 1), 8)
    blocker = Request(_prompt(2, 2), 8)
    batcher.submit(active_req)
    batcher.submit(blocker)
    batcher.submit(queued_req)  # stays queued: max_active=2
    batcher.step()
    assert batcher.stats()["active"] == 2 and batcher.stats()["queued"] == 1
    active_req.cancelled = True
    queued_req.cancelled = True
    batcher.drain()
    assert active_req.error == "cancelled mid-decode"
    assert queued_req.error == "cancelled before admission"
    assert len(active_req.tokens) < 8  # stopped early, slot freed
    assert blocker.error is None and len(blocker.tokens) == 8
    assert engine.cache.stats()["live_sessions"] == 0
