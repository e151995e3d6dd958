"""Mesh-spanning serving (ISSUE 14): the tensor-parallel mesh replica
(``ServeEngine(mesh_shards=N)`` — params + state-cache slots sharded
over a ("model",) device mesh via the training GSPMD specs) and the
remote-replica RPC transport (serve/remote.py) behind the router.

Pins: token-identical greedy AND temperature-sampled parity of the
sharded engine vs the single-device engine vs models/generate.py on the
conftest virtual devices; shard-axis compile keys; the loud (counted)
pallas→scan fallback on sharded engines; detach/restore and tier
spill/fill over sharded slots; the router treating a mesh replica as
just another replica; and the 2-process host-kill drill — SIGKILLing a
remote replica host loses ZERO kept sessions (continuations resume
token-identically from the shared ``--session-dir`` disk tier on the
survivor)."""

import os
import sys
import tempfile
import threading
import time

import jax
import numpy as np
import pytest

from lstm_tensorspark_tpu.models import LMConfig, init_lm, make_generate_fn
from lstm_tensorspark_tpu.obs import MetricsRegistry
from lstm_tensorspark_tpu.serve import (
    RemoteReplica,
    SamplingParams,
    ServeEngine,
    ServeServer,
)
from lstm_tensorspark_tpu.serve.engine import GREEDY
from lstm_tensorspark_tpu.serve.server import make_http_server
from lstm_tensorspark_tpu.serve.state_cache import (
    session_file_path as _session_file,
)
from tools.serve_proc import boot_serve_http_or_raise

_CFG = LMConfig(vocab_size=31, hidden_size=16, num_layers=2)
SHARDS = 2


@pytest.fixture(scope="module")
def params():
    return init_lm(jax.random.PRNGKey(5), _CFG)


def _engine(params, shards, *, seed=0, **kw):
    kw.setdefault("num_slots", 8)
    kw.setdefault("prefill_buckets", (4, 8))
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("registry", MetricsRegistry())
    return ServeEngine(params, _CFG, rng_seed=seed, mesh_shards=shards,
                       **kw)


def _server(engine, **kw):
    kw.setdefault("max_active", 4)
    kw.setdefault("queue_size", 16)
    kw.setdefault("window_ladder", (1, 4))
    return ServeServer(engine, **kw)


def _prompts(n, seed=0, lo=2, hi=8):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, _CFG.vocab_size,
                        size=rng.randint(lo, hi + 1)).astype(np.int32)
            for _ in range(n)]


def _serve_all(server, prompts, sampling=GREEDY, max_new=6):
    out = []
    with server:
        server.warmup(sampling, prompt_lens=(8,))
        for p in prompts:
            out.append(list(server.generate(
                p, max_new_tokens=max_new, sampling=sampling).tokens))
    return out


# ---- parity: sharded engine vs single-device vs models/generate --------


def test_mesh_greedy_parity_vs_single_and_generate(params):
    prompts = _prompts(4, seed=1)
    single = _serve_all(_server(_engine(params, 1)), prompts)
    mesh = _serve_all(_server(_engine(params, SHARDS)), prompts)
    assert mesh == single
    gen = make_generate_fn(_CFG, max_new_tokens=6, greedy=True)
    ref = [
        np.asarray(gen(params, p[None, :], jax.random.PRNGKey(0))
                   )[0, p.size:].tolist()
        for p in prompts
    ]
    assert mesh == ref


def test_mesh_sampled_parity(params):
    """Temperature-sampled parity: same engine rng chain + same dispatch
    order ⇒ the sharded engine must emit the SAME tokens (the Gumbel
    draws are identical; a sharded logits psum must not flip any
    argmax-after-noise)."""
    sa = SamplingParams(temperature=0.8)
    prompts = _prompts(4, seed=2)

    def engine_tokens(engine):
        engine.warmup(sa, prompt_lens=(8,), windows=(4,))
        toks = []
        for i, p in enumerate(prompts):
            sid = f"x{i}"
            slot, fresh = engine.cache.acquire_pinned(sid)
            first = int(engine.prefill([(slot, fresh, p)], sa)[0][0])
            win = engine.decode_window([slot], [first], [5],
                                       sampling=sa, window=4)
            row = engine.fetch_window(win)[0]
            toks.append([first] + [int(t) for t in row if t >= 0])
            engine.cache.release(sid)
        return toks

    assert (engine_tokens(_engine(params, 1, seed=7))
            == engine_tokens(_engine(params, SHARDS, seed=7)))


def test_mesh_compile_keys_carry_shard_axis(params):
    e = _engine(params, SHARDS)
    e.warmup(GREEDY, prompt_lens=(4,), windows=(4,))
    keys = set(e.compile_counts)
    assert keys, "warmup compiled nothing"
    assert all(k[-1] == SHARDS for k in keys), keys
    assert any(k[0] == "decode_window" for k in keys)
    assert e.stats()["mesh_shards"] == SHARDS
    # single-device engines keep the legacy key arity
    e1 = _engine(params, 1)
    e1.warmup(GREEDY, prompt_lens=(4,), windows=(4,))
    assert all(k[-1] != SHARDS or isinstance(k[-1], tuple)
               for k in e1.compile_counts)


def test_mesh_pallas_falls_back_loudly(params, capsys):
    """--decode-kernel pallas on a sharded engine: boot-time log line,
    every window dispatched as the scan program, fallbacks counted —
    never a crash, never a silent re-resolve."""
    e = _engine(params, SHARDS, decode_kernel="pallas")
    assert "not supported on a 2-shard mesh engine" in capsys.readouterr().out
    assert e.decode_kernel == "pallas"  # the request is recorded honestly
    e.warmup(GREEDY, prompt_lens=(4,), windows=(4,))
    assert e.decode_window_scan_fallbacks > 0
    assert not any(k[0] == "decode_window_pallas" for k in e.compile_counts)
    # "auto" resolves to scan on a mesh engine without counting fallbacks
    ea = _engine(params, SHARDS, decode_kernel="auto")
    assert ea.decode_kernel == "scan"
    ea.warmup(GREEDY, prompt_lens=(4,), windows=(4,))
    assert ea.decode_window_scan_fallbacks == 0


def test_mesh_engine_rejects_bad_shapes(params):
    with pytest.raises(ValueError, match="not divisible"):
        ServeEngine(params, LMConfig(vocab_size=31, hidden_size=15),
                    mesh_shards=2, registry=MetricsRegistry())
    with pytest.raises(ValueError, match="device"):
        _engine(params, SHARDS, device=jax.devices()[0])


# ---- session lifecycle over sharded slots ------------------------------


def test_mesh_detach_restore_token_identical(params):
    e = _engine(params, SHARDS)
    srv = _server(e)
    prompt = np.arange(1, 6, dtype=np.int32)
    with srv:
        srv.warmup(prompt_lens=(8,))
        first = srv.generate(prompt, max_new_tokens=3, keep_session=True)
        sid = first.session_id
        state = e.detach_session(sid)
        assert state.h.shape == (_CFG.num_layers, _CFG.hidden_size)
        e.restore_session(sid, state)
        cont = srv.generate([first.tokens[-1]], max_new_tokens=3,
                            session_id=sid, keep_session=True)
    gen = make_generate_fn(_CFG, max_new_tokens=6, greedy=True)
    ref = np.asarray(gen(params, prompt[None, :], jax.random.PRNGKey(0))
                     )[0, prompt.size:]
    assert list(first.tokens) + list(cont.tokens) == ref.tolist()


def test_mesh_tier_spill_fill_token_identical(params, tmp_path):
    """Tier fill/spill over SHARDED slots: 3 kept sessions over 2 slots
    force evictions (async spill of sharded rows) and continuation
    fills — every conversation must match the ample-slots single-device
    reference token for token."""

    def conversations(engine, max_active=2):
        srv = _server(engine, max_active=max_active)
        toks = []
        with srv:
            srv.warmup(prompt_lens=(8,))
            sids = []
            for i in range(3):
                r = srv.generate([i + 1, i + 2, 3], max_new_tokens=4,
                                 keep_session=True)
                sids.append(r.session_id)
                toks.append(list(r.tokens))
            for _ in range(2):
                for i, sid in enumerate(sids):
                    r = srv.generate([toks[i][-1]], max_new_tokens=4,
                                     session_id=sid, keep_session=True)
                    toks[i].extend(r.tokens)
        return toks

    mesh = conversations(_engine(
        params, SHARDS, num_slots=2,
        session_dir=str(tmp_path / "mesh_tiers")))
    ref = conversations(_engine(params, 1), max_active=4)
    assert mesh == ref


# ---- the router's view of a mesh replica -------------------------------


def test_router_treats_mesh_replica_as_one_replica(params):
    """A mixed fleet — replica 0 sharded, replica 1 single-device —
    behind one router: health fans in 2 replicas, both serve traffic,
    and greedy output is token-identical to models/generate.py whichever
    replica decodes it."""
    reg = MetricsRegistry()
    engines = [
        _engine(params, SHARDS, seed=0, registry=reg),
        _engine(params, 1, seed=1, registry=reg),
    ]
    srv = _server(engines)
    prompts = _prompts(6, seed=3)
    results: list = [None] * len(prompts)
    replicas: list = [None] * len(prompts)
    with srv:
        srv.warmup(prompt_lens=(8,))
        h = srv.health()
        assert h["status"] == "ok" and h["replicas_total"] == 2

        def one(i):
            r = srv.generate(prompts[i], max_new_tokens=6)
            results[i] = list(r.tokens)
            replicas[i] = r.replica

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        routed = srv.router.stats()["routed"]
    assert set(replicas) == {0, 1}, replicas
    assert sum(routed.values()) == len(prompts)
    gen = make_generate_fn(_CFG, max_new_tokens=6, greedy=True)
    for p, got in zip(prompts, results):
        ref = np.asarray(gen(params, p[None, :], jax.random.PRNGKey(0))
                         )[0, p.size:]
        assert got == ref.tolist()


# ---- remote-replica RPC transport --------------------------------------


def test_remote_replica_inprocess_rpc(params):
    """The RPC surface against an in-process peer: heartbeat liveness,
    generate RPC parity, session affinity probes, and the remote shim's
    batcher-stat mirror feeding the front's aggregate stats."""
    peer_eng = _engine(params, 1, seed=0)
    peer = _server(peer_eng)
    httpd = make_http_server(peer, "127.0.0.1", 0)
    host, port = httpd.server_address[:2]
    http_thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    front_eng = _engine(params, 1, seed=1)
    front = ServeServer(front_eng, max_active=4, queue_size=16,
                        window_ladder=(1, 4),
                        remote_replicas=(f"http://{host}:{port}",))
    # the RPC shim IS a replica: the router sees two
    assert len(front.replicas) == 2
    assert isinstance(front.replicas[1], RemoteReplica)
    try:
        with peer:
            peer.warmup(prompt_lens=(8,))
            http_thread.start()
            with front:
                front.warmup(prompt_lens=(8,))
                deadline = time.monotonic() + 10
                while (front.replicas[1].batcher.last_heartbeat is None
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
                assert front.replicas[1].batcher.last_heartbeat is not None
                h = front.health()
                assert h["replicas_healthy"] == 2
                # pin enough traffic to hit BOTH replicas (fresh requests
                # go least-loaded, round-robin on ties)
                prompts = _prompts(4, seed=4)
                homes, toks, sids = [], [], []
                for p in prompts:
                    r = front.generate(p, max_new_tokens=4,
                                       keep_session=True)
                    homes.append(r.replica)
                    toks.append(list(r.tokens))
                    sids.append(r.session_id)
                assert set(homes) == {0, 1}, homes
                # affinity: continuations land on the session's host
                for i, sid in enumerate(sids):
                    r = front.generate([toks[i][-1]], max_new_tokens=4,
                                       session_id=sid, keep_session=True)
                    assert r.replica == homes[i]
                    toks[i].extend(r.tokens)
                # the aggregate mirrors the remote's counters at the
                # heartbeat cadence — give one poll time to land
                deadline = time.monotonic() + 10
                while (front.stats()["batcher"]["completed"]
                       < len(prompts) * 2
                       and time.monotonic() < deadline):
                    time.sleep(0.1)
                st = front.stats()
                assert st["batcher"]["completed"] >= len(prompts) * 2
                remote_stats = front.replicas[1].batcher.stats()
                assert remote_stats["rpc_completed"] >= 2
                gen = make_generate_fn(_CFG, max_new_tokens=8, greedy=True)
                for p, got in zip(prompts, toks):
                    ref = np.asarray(
                        gen(params, p[None, :], jax.random.PRNGKey(0))
                    )[0, p.size:]
                    assert got == ref.tolist()
    finally:
        httpd.shutdown()
        httpd.server_close()


_HOST_ARGS = [
    "serve", "--http", "--port", "0", "--vocab-size", "31",
    "--hidden-units", "16", "--num-layers", "2", "--seed", "5",
    "--prefill-buckets", "4,8", "--batch-buckets", "1,2",
    "--decode-window", "1", "--prefix-cache", "off",
    "--num-slots", "8", "--max-active", "4",
]


def _boot_host(session_dir, timeout=180.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "lstm_tensorspark_tpu.cli",
           *_HOST_ARGS, "--session-dir", session_dir]
    return boot_serve_http_or_raise(cmd, env, timeout)




def test_remote_host_kill_loses_no_kept_session(params):
    """THE 2-process drill (acceptance gate): kept conversations spread
    over a local replica and a remote replica HOST (a real `cli serve
    --http` subprocess) sharing one --session-dir; the host is
    SIGKILLed mid-conversation; every continuation must complete on the
    survivor, token-identical to an uninterrupted run — host death
    generalises PR 7's replica death because the shared disk tier makes
    kept sessions claimable by any host."""
    work = tempfile.mkdtemp(prefix="serve_mesh_hostkill_")
    proc, base = _boot_host(work)
    front = None
    try:
        front_eng = _engine(params, 1, seed=0, session_dir=work)
        front = ServeServer(front_eng, max_active=4, queue_size=16,
                            window_ladder=(1,), remote_replicas=(base,))
        with front:
            front.warmup(prompt_lens=(4,))
            sids, toks, homes = [], [], []
            for i in range(4):
                r = front.generate([i + 1, i + 2, 3], max_new_tokens=4,
                                   keep_session=True, timeout=60)
                sids.append(r.session_id)
                toks.append(list(r.tokens))
                homes.append(r.replica)
            assert 1 in homes, f"nothing routed to the remote: {homes}"
            t_turn = time.time()
            for i, sid in enumerate(sids):
                r = front.generate([toks[i][-1]], max_new_tokens=4,
                                   session_id=sid, keep_session=True,
                                   timeout=60)
                assert r.replica == homes[i]  # affinity crossed the wire
                toks[i].extend(r.tokens)

            # durability boundary: await every session's write-behind
            # checkpoint — file newer than the turn AND quiescent for
            # 1 s, so a lagging previous-boundary write cannot
            # masquerade as the turn's checkpoint — before the crash
            # (the drill tests host DEATH, not an unflushed
            # write-behind)
            deadline = time.time() + 30

            def flushed():
                mtimes = []
                for sid in sids:
                    p = _session_file(work, sid)
                    if not os.path.exists(p):
                        return False
                    mtimes.append(os.path.getmtime(p))
                return (min(mtimes) >= t_turn
                        and time.time() - max(mtimes) > 1.0)

            while not flushed() and time.time() < deadline:
                time.sleep(0.1)
            assert flushed(), "write-behind checkpoints never landed"

            proc.kill()  # SIGKILL: host death, no graceful flush
            proc.wait()

            # zero kept sessions lost: every continuation (including the
            # dead host's) completes on the survivor from the shared tier
            for i, sid in enumerate(sids):
                r = front.generate([toks[i][-1]], max_new_tokens=4,
                                   session_id=sid, keep_session=True,
                                   timeout=60)
                assert r.replica == 0
                toks[i].extend(r.tokens)

            # the heartbeat poller exits and the sweep retires the host
            deadline = time.monotonic() + 15
            while (1 not in front.router.stats()["retired"]
                   and time.monotonic() < deadline):
                front.router.sweep()
                time.sleep(0.2)
            assert 1 in front.router.stats()["retired"]
            assert front.health()["replicas_healthy"] == 1

        # token identity vs the uninterrupted single-replica run
        ref_srv = _server(_engine(params, 1, seed=0), window_ladder=(1,))
        ref = []
        with ref_srv:
            ref_srv.warmup(prompt_lens=(4,))
            rsids = []
            for i in range(4):
                r = ref_srv.generate([i + 1, i + 2, 3], max_new_tokens=4,
                                     keep_session=True)
                rsids.append(r.session_id)
                ref.append(list(r.tokens))
            for _ in range(2):
                for i, sid in enumerate(rsids):
                    r = ref_srv.generate([ref[i][-1]], max_new_tokens=4,
                                         session_id=sid, keep_session=True)
                    ref[i].extend(r.tokens)
        assert toks == ref
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
