#!/usr/bin/env python
"""Serve-plane chaos drill: machine-check the robustness invariants the
replicated/tiered serve stack promises, under INJECTED faults
(resilience/faults.py serve kinds), deterministically on CPU.

Four phases, each building a fresh in-process stack from one fixed seed:

1. **replica death** — a 2-replica ``--session-dir`` stack serves kept
   conversations; the replica owning them is killed mid-run
   (``replica_die@RxK``); the router's retirement (detach/restore
   migration + shared-disk persistence) must lose ZERO kept sessions and
   every continuation must be token-identical to an uninterrupted run.
2. **disk errors** — an injected ``disk_write_err`` on the write-behind
   checkpoint must surface as
   ``serve_tier_lost_total{reason="disk_error"}`` with correct tokens
   still served (durability lost, correctness kept); an injected
   ``session_corrupt`` must be QUARANTINED at fill time on a fresh boot
   and fail the continuation honestly — never wrong tokens.
3. **latency faults** — ``slow_readback`` + ``spill_stall`` inject
   delays into the decode-window fetch and the spill worker; outputs
   stay token-identical and ``flush()`` stays a real durability barrier.
4. **burst shed** — a 4x open-loop burst with mixed admission classes:
   the priority class p99 TTFT must hold the configured SLO while
   best-effort sheds with honest ``Retry-After`` 429s; the same burst is
   replayed with the old indiscriminate-FIFO settings for contrast, and
   both land in the report (stdout; ``--json`` also writes it to a file).
5. **host death** (``host_die`` fault kind) — a REMOTE replica (a real
   ``cli serve --http`` subprocess behind the front router via the RPC
   transport, serve/remote.py) is SIGKILLed mid-conversation; the
   shared ``--session-dir`` disk tier must hand every kept session to
   the surviving local replica, token-identical to an uninterrupted
   run — PR 7's replica-death invariant generalized to a dead HOST.
6. **partition/heal** (``net_blackhole`` + ``net_drop``, ISSUE 17) — a
   remote replica host is BLACKHOLED (alive, unreachable)
   mid-conversation: the per-peer circuit must open within a few failed
   probes, continuations must route around it fast (never waiting out
   the generate timeout, zero kept sessions lost via the shared
   ``--session-dir``), a burst must shed with honest ``Retry-After``;
   on heal the peer must REJOIN without restart (probe hysteresis
   closes the circuit, fresh traffic routes there again) and the full
   conversation stays token-identical. A dropped-response generate then
   proves exactly-once: the transport retries under the request_id and
   the peer replays its settled reply — ZERO duplicate decodes.

Wired into tools/verify.sh after the serve smoke (sequenced, never
concurrent with the timed suite). Exit 0 on PASS, 1 on any violated
invariant, with the failing invariant + the fault spec that reproduces
it printed (see docs/OPERATIONS.md "Chaos drill failed").

Usage::

    JAX_PLATFORMS=cpu python tools/chaos_serve.py [--json OUT] \
        [--slo-ms 1000] [--seed 0]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

from lstm_tensorspark_tpu.models import LMConfig, init_lm  # noqa: E402
from lstm_tensorspark_tpu.obs import MetricsRegistry  # noqa: E402
from lstm_tensorspark_tpu.resilience import faults  # noqa: E402
from lstm_tensorspark_tpu.serve import (  # noqa: E402
    ServeEngine,
    ServeServer,
    run_loadgen,
)
from lstm_tensorspark_tpu.serve.state_cache import (  # noqa: E402
    session_file_path as _session_file,
)
from tools.serve_proc import boot_serve_http_or_raise  # noqa: E402

_CFG = LMConfig(vocab_size=41, hidden_size=16, num_layers=1)
_SEED = 3  # params seed — every stack (chaos + reference) shares params


def _build(params, n, *, session_dir=None, num_slots=8, max_active=4,
           queue_size=16, **server_kw):
    reg = MetricsRegistry()
    engines = [
        ServeEngine(params, _CFG, num_slots=num_slots,
                    prefill_buckets=(4, 8), batch_buckets=(1, 2, 4),
                    rng_seed=i, registry=reg, session_dir=session_dir,
                    replica=i)
        for i in range(n)
    ]
    return ServeServer(engines if n > 1 else engines[0],
                       max_active=max_active, queue_size=queue_size,
                       **server_kw)


def _create_kept(server, i):
    """One kept session with a per-index prompt; returns (sid, tokens,
    home replica)."""
    r = server.generate([i + 1, i + 2, 3], max_new_tokens=4,
                        keep_session=True)
    return r.session_id, list(r.tokens), r.replica


def _continue_kept(server, sid, last_tok):
    r = server.generate([last_tok], max_new_tokens=4, session_id=sid,
                        keep_session=True)
    return list(r.tokens)


def _reference_tokens(params, n_sessions, turns):
    """The uninterrupted single-replica run of the same conversation
    schedule — the token-identity oracle for every fault phase."""
    ref = _build(params, 1)
    out = []
    with ref:
        sids = []
        for i in range(n_sessions):
            sid, toks, _ = _create_kept(ref, i)
            sids.append(sid)
            out.append(toks)
        for _ in range(turns):
            for i, sid in enumerate(sids):
                out[i].extend(_continue_kept(ref, sid, out[i][-1]))
    return out


# ---- phase 1: replica death --------------------------------------------


def _phase_replica_death(params, seed, failures):
    work = tempfile.mkdtemp(prefix="chaos_serve_death_")
    n_sessions = 4
    res = {"sessions": n_sessions}
    try:
        srv = _build(params, 2, session_dir=work)
        with srv:
            sids, toks, homes = [], [], []
            for i in range(n_sessions):
                sid, t, home = _create_kept(srv, i)
                sids.append(sid)
                toks.append(t)
                homes.append(home)
            for i, sid in enumerate(sids):  # one pre-death turn
                toks[i].extend(_continue_kept(srv, sid, toks[i][-1]))
            victim = homes[0]
            spec = f"replica_die@{victim}x1;seed@{seed}"
            res["fault_spec"] = spec
            res["victim"] = victim
            res["victim_sessions"] = sum(1 for h in homes if h == victim)
            faults.arm(spec)
            t = srv.replicas[victim].thread
            t.join(timeout=15.0)
            faults.disarm()
            if t.is_alive():
                failures.append(
                    f"replica_death: {spec} never killed the scheduler")
                return res
            srv.health()  # piggybacked sweep retires + migrates
            lost = 0
            for i, sid in enumerate(sids):  # post-death continuations
                try:
                    toks[i].extend(_continue_kept(srv, sid, toks[i][-1]))
                except Exception as e:
                    lost += 1
                    failures.append(
                        f"replica_death: kept session {sid!r} lost after "
                        f"{spec}: {type(e).__name__}: {e}")
            res["lost_sessions"] = lost
            res["router"] = {
                k: srv.router.stats()[k]
                for k in ("retired", "migrated_sessions", "lost_sessions",
                          "requeued", "failed_on_death")}
        ref = _reference_tokens(params, n_sessions, turns=2)
        res["token_identical"] = toks == ref
        if toks != ref:
            failures.append(
                f"replica_death: continuations diverged from the "
                f"uninterrupted run (spec {res['fault_spec']})")
    finally:
        faults.disarm()
        shutil.rmtree(work, ignore_errors=True)
    return res


# ---- phase 2: disk-tier faults -----------------------------------------


def _phase_disk_faults(params, seed, failures):
    res = {}
    # ---- write error: durability lost, correctness kept ----------------
    work = tempfile.mkdtemp(prefix="chaos_serve_disk_")
    try:
        srv = _build(params, 1, session_dir=work)
        with srv:
            sid, toks, _ = _create_kept(srv, 0)
            srv.engine.tiers.flush(timeout=15.0)
            spec = f"disk_write_err@1;seed@{seed}"
            res["write_fault_spec"] = spec
            faults.arm(spec)
            toks.extend(_continue_kept(srv, sid, toks[-1]))
            srv.engine.tiers.flush(timeout=15.0)
            faults.disarm()
            ts = srv.engine.tiers.stats()
            res["disk_errors"] = ts["disk_errors"]
            key = 'serve_tier_lost_total{reason="disk_error",replica="0"}'
            res["disk_error_metric"] = srv.engine.metrics.summaries().get(
                key, 0)
            if ts["disk_errors"] < 1 or res["disk_error_metric"] < 1:
                failures.append(
                    f"disk_faults: {spec} did not surface as "
                    f"serve_tier_lost_total{{reason=\"disk_error\"}} "
                    f"(stats {ts['disk_errors']}, metric "
                    f"{res['disk_error_metric']})")
            # correctness kept: the state never left RAM/device
            toks.extend(_continue_kept(srv, sid, toks[-1]))
        ref = _reference_tokens(params, 1, turns=2)
        res["write_token_identical"] = [toks] == ref
        if [toks] != ref:
            failures.append(
                f"disk_faults: tokens diverged after a failed disk write "
                f"(spec {spec}) — durability trouble must never cost "
                "correctness")
    finally:
        faults.disarm()
        shutil.rmtree(work, ignore_errors=True)
    # ---- corrupt session file: quarantine + honest loss ----------------
    work = tempfile.mkdtemp(prefix="chaos_serve_corrupt_")
    try:
        spec = f"session_corrupt@1;seed@{seed}"
        res["corrupt_fault_spec"] = spec
        faults.arm(spec)
        srv = _build(params, 1, session_dir=work)
        with srv:
            sid, toks, _ = _create_kept(srv, 0)
            srv.engine.tiers.flush(timeout=15.0)
        faults.disarm()
        # fresh boot on the same dir — the restart that must detect it
        srv2 = _build(params, 1, session_dir=work)
        with srv2:
            honest = False
            try:
                _continue_kept(srv2, sid, toks[-1])
                failures.append(
                    f"disk_faults: corrupt session file served a "
                    f"continuation (spec {spec}) — wrong tokens risk")
            except RuntimeError as e:
                honest = "unknown session" in str(e)
                if not honest:
                    failures.append(
                        f"disk_faults: corrupt-file continuation failed "
                        f"with the wrong error: {e}")
            res["honest_failure"] = honest
            ts = srv2.engine.tiers.stats()
            # the corruption is detected at whichever layer reads it
            # first: a damaged HEADER is quarantined by the fresh boot's
            # startup scan (the continuation then counts a miss), a
            # damaged BODY passes the scan and is quarantined at fill
            # time (counted corrupt). Both are the honest path.
            res["corrupt_counted"] = ts["corrupt"]
            res["miss_counted"] = ts["misses"]
        quarantined = glob.glob(os.path.join(work, "*.quarantined"))
        res["quarantined"] = len(quarantined)
        if not quarantined:
            failures.append(
                f"disk_faults: no *.quarantined file after {spec}")
        if res["corrupt_counted"] + res["miss_counted"] < 1:
            failures.append(
                "disk_faults: the corrupt file's continuation was "
                "counted neither corrupt nor miss")
    finally:
        faults.disarm()
        shutil.rmtree(work, ignore_errors=True)
    return res


# ---- phase 3: latency faults (slow readback, spill stall) ---------------


def _phase_latency_faults(params, seed, failures):
    res = {}
    work = tempfile.mkdtemp(prefix="chaos_serve_latency_")
    try:
        spec = f"slow_readback@1x200;spill_stall@1x1;seed@{seed}"
        res["fault_spec"] = spec
        # 2 slots + 3 kept sessions forces evictions (spills) and fills
        srv = _build(params, 1, session_dir=work, num_slots=2,
                     max_active=2)
        faults.arm(spec)
        toks = []
        with srv:
            sids = []
            for i in range(3):
                sid, t, _ = _create_kept(srv, i)
                sids.append(sid)
                toks.append(t)
            for _ in range(2):
                for i, sid in enumerate(sids):
                    toks[i].extend(_continue_kept(srv, sid, toks[i][-1]))
            flushed = srv.engine.tiers.flush(timeout=30.0)
            res["flush_ok"] = bool(flushed)
            if not flushed:
                failures.append(
                    f"latency_faults: flush() wedged under {spec} — the "
                    "durability barrier must survive a stalled worker")
        faults.disarm()
        # reference needs the same slot pressure (3 sessions over 2
        # slots re-prefill nothing — tiers restore exactly), so the
        # plain 1-replica reference with ample slots is still the oracle
        ref = _reference_tokens(params, 3, turns=2)
        res["token_identical"] = toks == ref
        if toks != ref:
            failures.append(
                f"latency_faults: tokens diverged under {spec} — "
                "injected latency must never change output")
    finally:
        faults.disarm()
        shutil.rmtree(work, ignore_errors=True)
    return res


# ---- phase 5: host death (remote replica killed mid-conversation) -------


_HOST_ARGS = [
    "serve", "--http", "--port", "0", "--vocab-size", str(_CFG.vocab_size),
    "--hidden-units", str(_CFG.hidden_size),
    "--num-layers", str(_CFG.num_layers), "--seed", str(_SEED),
    "--prefill-buckets", "4,8", "--batch-buckets", "1,2",
    "--decode-window", "1", "--prefix-cache", "off",
    "--num-slots", "8", "--max-active", "4",
]


def _boot_remote_host(session_dir: str, timeout: float = 180.0):
    """Boot a replica-host subprocess (same params as the in-process
    reference: the CLI re-derives them from --seed/--vocab-size/...)
    and wait for its address line (tools/serve_proc.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "lstm_tensorspark_tpu.cli",
           *_HOST_ARGS, "--session-dir", session_dir]
    return boot_serve_http_or_raise(cmd, env, timeout)




def _phase_host_death(params, seed, failures):
    work = tempfile.mkdtemp(prefix="chaos_serve_hostdie_")
    n_sessions = 4
    res = {"sessions": n_sessions, "fault_spec": "host_die@remote"}
    proc = None
    try:
        proc, base = _boot_remote_host(work)
        res["remote_url"] = base
        from lstm_tensorspark_tpu.serve import ServeServer

        reg = MetricsRegistry()
        eng = ServeEngine(params, _CFG, num_slots=8,
                          prefill_buckets=(4, 8), batch_buckets=(1, 2),
                          rng_seed=0, registry=reg, session_dir=work,
                          replica=0)
        srv = ServeServer(eng, max_active=4, queue_size=16,
                          window_ladder=(1,), remote_replicas=(base,))
        with srv:
            sids, toks, homes = [], [], []
            for i in range(n_sessions):
                sid, t, home = _create_kept(srv, i)
                sids.append(sid)
                toks.append(t)
                homes.append(home)
            res["remote_sessions"] = sum(1 for h in homes if h == 1)
            if res["remote_sessions"] < 1:
                failures.append(
                    "host_death: no kept session landed on the remote "
                    f"replica (homes {homes}) — the kill would test "
                    "nothing")
                return res
            t_turn = time.monotonic()
            # wall clock on purpose: compared against file MTIMES below
            # (the checkpoint-flushed probe) — monotonic has no epoch
            t_turn_wall = time.time()  # graftlint: disable=wallclock-timing
            for i, sid in enumerate(sids):  # one pre-death turn
                toks[i].extend(_continue_kept(srv, sid, toks[i][-1]))
            # durability boundary: the drill tests host DEATH, not an
            # unflushed write-behind — await every session's checkpoint
            # (file mtime at/after the turn) before pulling the trigger
            deadline = time.monotonic() + 30

            def flushed():
                # every file strictly after the turn started (a file
                # from a PREVIOUS boundary would resume the
                # conversation without tokens the client already saw)
                # AND quiescent for 1 s: the write-behind worker merges
                # a superseded capture and rewrites within ~100 ms, so
                # a lagging creation-boundary write landing after
                # t_turn_wall cannot masquerade as the turn's
                # checkpoint past the quiet window
                mtimes = []
                for sid in sids:
                    p = _session_file(work, sid)
                    if not os.path.exists(p):
                        return False
                    mtimes.append(os.path.getmtime(p))
                return (min(mtimes) >= t_turn_wall
                        and time.time()  # graftlint: disable=wallclock-timing
                        - max(mtimes) > 1.0)

            while not flushed() and time.monotonic() < deadline:
                time.sleep(0.1)
            res["checkpoints_flushed"] = flushed()
            if not flushed():
                failures.append(
                    "host_death: write-behind session checkpoints never "
                    "landed on the shared --session-dir")
                return res
            proc.kill()  # SIGKILL mid-conversation: host death
            proc.wait()
            res["kill_after_s"] = round(time.monotonic() - t_turn, 2)
            lost = 0
            for i, sid in enumerate(sids):  # post-death continuations
                try:
                    toks[i].extend(_continue_kept(srv, sid, toks[i][-1]))
                except Exception as e:
                    lost += 1
                    failures.append(
                        f"host_death: kept session {sid!r} lost after "
                        f"the host kill: {type(e).__name__}: {e}")
            res["lost_sessions"] = lost
            # the heartbeat poller exits → the sweep retires the host
            deadline = time.monotonic() + 15
            while (1 not in srv.router.stats()["retired"]
                   and time.monotonic() < deadline):
                srv.router.sweep()
                time.sleep(0.2)
            rt = srv.router.stats()
            res["retired"] = rt["retired"]
            res["router"] = {k: rt[k] for k in
                             ("retired", "failed_on_death", "requeued")}
            if 1 not in rt["retired"]:
                failures.append(
                    "host_death: the dead host was never retired (the "
                    "heartbeat poller must exit and the sweep must "
                    "claim it)")
        ref = _reference_tokens(params, n_sessions, turns=2)
        res["token_identical"] = toks == ref
        if toks != ref:
            failures.append(
                "host_death: continuations diverged from the "
                "uninterrupted run (host_die@remote)")
    except Exception as e:
        failures.append(f"host_death: drill error: {type(e).__name__}: {e}")
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        shutil.rmtree(work, ignore_errors=True)
    return res


# ---- phase 6: partition / heal (blackholed remote host, ISSUE 17) -------


def _peer_heartbeat(base: str) -> dict:
    with urllib.request.urlopen(base + "/replica/heartbeat",
                                timeout=10.0) as resp:
        return json.loads(resp.read().decode("utf-8"))


def _peer_metric(base: str, token: str) -> float:
    """Scrape one sample from the peer's /metrics exposition."""
    with urllib.request.urlopen(base + "/metrics", timeout=10.0) as resp:
        text = resp.read().decode("utf-8")
    for line in text.splitlines():
        if line.startswith(token):
            return float(line.rsplit(None, 1)[-1])
    return 0.0


def _await_flushed(work, sids, t_turn_wall, timeout=30.0) -> bool:
    """Every kept session's checkpoint at/after the turn AND quiescent
    for 1 s (same durability boundary the host-death phase awaits)."""

    def flushed():
        mtimes = []
        for sid in sids:
            p = _session_file(work, sid)
            if not os.path.exists(p):
                return False
            mtimes.append(os.path.getmtime(p))
        return (min(mtimes) >= t_turn_wall
                and time.time()  # graftlint: disable=wallclock-timing
                - max(mtimes) > 1.0)

    deadline = time.monotonic() + timeout
    while not flushed() and time.monotonic() < deadline:
        time.sleep(0.1)
    return flushed()


def _phase_partition(params, seed, failures):
    """Blackhole a live remote host mid-conversation, prove the circuit
    opens and the router routes around it (fast, honestly, losing
    nothing), heal, prove it rejoins WITHOUT restart, then prove the
    request_id replay path decodes a dropped-response generate exactly
    once."""
    work = tempfile.mkdtemp(prefix="chaos_serve_partition_")
    n_sessions = 4
    res = {"sessions": n_sessions,
           "fault_spec": f"net_blackhole@1 then net_drop@1;seed@{seed}"}
    proc = None
    try:
        proc, base = _boot_remote_host(work)
        res["remote_url"] = base
        reg = MetricsRegistry()
        eng = ServeEngine(params, _CFG, num_slots=8,
                          prefill_buckets=(4, 8), batch_buckets=(1, 2),
                          rng_seed=0, registry=reg, session_dir=work,
                          replica=0)
        srv = ServeServer(eng, max_active=4, queue_size=16,
                          window_ladder=(1,), remote_replicas=(base,),
                          remote_poll_interval_s=0.1,
                          remote_rpc_timeout_s=1.0,
                          remote_timeout_s=30.0)
        with srv:
            shim = srv.replicas[1].batcher
            sids, toks, homes = [], [], []
            for i in range(n_sessions):
                sid, t, home = _create_kept(srv, i)
                sids.append(sid)
                toks.append(t)
                homes.append(home)
            res["remote_sessions"] = sum(1 for h in homes if h == 1)
            if res["remote_sessions"] < 1:
                failures.append(
                    "partition: no kept session landed on the remote "
                    f"replica (homes {homes}) — the blackhole would "
                    "test nothing")
                return res
            # wall clock on purpose: compared against file MTIMES (the
            # checkpoint-flushed probe) — monotonic has no epoch
            t_turn_wall = time.time()  # graftlint: disable=wallclock-timing
            for i, sid in enumerate(sids):  # one pre-partition turn
                toks[i].extend(_continue_kept(srv, sid, toks[i][-1]))
            res["checkpoints_flushed"] = _await_flushed(
                work, sids, t_turn_wall)
            if not res["checkpoints_flushed"]:
                failures.append(
                    "partition: write-behind session checkpoints never "
                    "landed on the shared --session-dir")
                return res
            routed_before = srv.router.stats()["routed"].get("1", 0)
            # ---- partition: blackhole the peer (until the heal) -------
            t_cut = time.monotonic()
            faults.arm(f"net_blackhole@1;seed@{seed}")
            deadline = time.monotonic() + 25
            while (shim.circuit.state() != "open"
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            res["seconds_to_open"] = round(time.monotonic() - t_cut, 2)
            res["circuit_opened"] = shim.circuit.state() == "open"
            if not res["circuit_opened"]:
                failures.append(
                    f"partition: the circuit never opened within "
                    f"{res['seconds_to_open']}s of the blackhole "
                    f"(open_after={shim.circuit.open_after} failed "
                    "probes expected)")
                return res
            # the partition is a route-around state, never a death:
            if not srv.replicas[1].thread.is_alive():
                failures.append(
                    "partition: the heartbeat poller exited on "
                    "partition-shaped failures (retirement must be "
                    "refused-only)")
            # continuations during the partition: every kept session —
            # including the peer's — must complete on the local replica
            # from the shared disk tier, fast (nobody waits out the 30s
            # generate timeout or queues behind the blackhole)
            lost = 0
            slow = 0.0
            for i, sid in enumerate(sids):
                t0 = time.monotonic()
                try:
                    toks[i].extend(_continue_kept(srv, sid, toks[i][-1]))
                except Exception as e:
                    lost += 1
                    failures.append(
                        f"partition: kept session {sid!r} lost during "
                        f"the partition: {type(e).__name__}: {e}")
                slow = max(slow, time.monotonic() - t0)
            res["lost_sessions"] = lost
            res["partition_continue_max_s"] = round(slow, 2)
            if slow >= 10.0:
                failures.append(
                    f"partition: a continuation took {slow:.1f}s during "
                    "the partition — routing around an open circuit "
                    "must not wait on the dead link")
            routed_mid = srv.router.stats()["routed"].get("1", 0)
            res["routed_remote_during_partition"] = (
                routed_mid - routed_before)
            if res["routed_remote_during_partition"] > 0:
                failures.append(
                    "partition: the router sent requests to the "
                    "blackholed peer while its circuit was open")
            # burst shed during the partition: capacity honestly halved,
            # overload answered with 429 + measured Retry-After
            shed_retry_after = []
            done = []

            def _burst_one(k):
                try:
                    srv.generate([k + 2, 5, 3], max_new_tokens=8,
                                 klass="best_effort", timeout=30.0)
                    done.append(k)
                except Exception as e:
                    ra = getattr(e, "retry_after_s", None)
                    if ra is not None:
                        shed_retry_after.append(float(ra))

            threads = [threading.Thread(target=_burst_one, args=(k,))
                       for k in range(32)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60.0)
            res["burst_completed"] = len(done)
            res["burst_shed"] = len(shed_retry_after)
            res["burst_retry_after_s_max"] = (
                round(max(shed_retry_after), 3) if shed_retry_after
                else None)
            if not shed_retry_after:
                failures.append(
                    "partition: a 32-request burst against the halved "
                    "fleet shed nothing — the admission bound must "
                    "exclude the partitioned peer's queue")
            elif min(shed_retry_after) <= 0:
                failures.append(
                    "partition: a shed carried a non-positive "
                    "Retry-After — the drain estimate must stay honest")
            # ---- heal: probes close the circuit, the peer rejoins -----
            t_heal = time.monotonic()
            faults.disarm()
            deadline = time.monotonic() + 20
            while (shim.circuit.state() != "closed"
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            res["seconds_to_close"] = round(time.monotonic() - t_heal, 2)
            res["circuit_closed"] = shim.circuit.state() == "closed"
            res["circuit_opened_total"] = shim.circuit.opened_total
            res["circuit_closed_total"] = shim.circuit.closed_total
            res["rejoined_without_restart"] = (
                res["circuit_closed"] and proc.poll() is None)
            if not res["rejoined_without_restart"]:
                failures.append(
                    "partition: the peer never rejoined after the heal "
                    f"(circuit {shim.circuit.state()!r}, process "
                    f"{'alive' if proc.poll() is None else 'dead'}) — "
                    "rejoin must need no restart")
                return res
            # fresh traffic routes to the healed peer again
            res["fresh_routed_to_peer"] = False
            for k in range(20):
                r = srv.generate([k + 3, 7, 3], max_new_tokens=2)
                if r.replica == 1:
                    res["fresh_routed_to_peer"] = True
                    break
            if not res["fresh_routed_to_peer"]:
                failures.append(
                    "partition: no fresh session routed to the healed "
                    "peer — rejoin is incomplete")
            for i, sid in enumerate(sids):  # post-heal turn
                toks[i].extend(_continue_kept(srv, sid, toks[i][-1]))
            # ---- exactly-once: drop a generate response, replay it ----
            hb0 = _peer_heartbeat(base)
            completed0 = int(hb0["batcher"]["completed"])
            hits0 = _peer_metric(
                base, 'serve_replay_dedup_total{result="hit"}')
            retries0 = shim.stats()["rpc_retries"]
            faults.arm(f"net_drop@1;seed@{seed}")
            try:
                dropped = None
                for k in range(12):
                    r = srv.generate([k + 4, 6, 3], max_new_tokens=3)
                    if r.replica == 1:
                        dropped = r
                        break
                if dropped is None:
                    failures.append(
                        "partition: no generate routed to the peer for "
                        "the drop — dedup untested")
                    return res
            finally:
                faults.disarm()
            retries = shim.stats()["rpc_retries"] - retries0
            hb1 = _peer_heartbeat(base)
            completed1 = int(hb1["batcher"]["completed"])
            hits1 = _peer_metric(
                base, 'serve_replay_dedup_total{result="hit"}')
            res["dedup"] = {
                "tokens_delivered": len(dropped.tokens),
                "transport_retries": retries,
                "peer_completed_delta": completed1 - completed0,
                "replay_hits": hits1 - hits0,
                "duplicate_decodes": max(0, completed1 - completed0 - 1),
            }
            if len(dropped.tokens) != 3:
                failures.append(
                    "partition: the dropped-then-replayed generate "
                    f"delivered {len(dropped.tokens)} tokens, wanted 3")
            if retries < 1:
                failures.append(
                    "partition: the transport never retried the "
                    "dropped response — the replay path is untested")
            if res["dedup"]["duplicate_decodes"] != 0:
                failures.append(
                    f"partition: the peer decoded the same request_id "
                    f"{completed1 - completed0} times — replay dedup "
                    "must make delivery exactly-once")
            if hits1 - hits0 < 1:
                failures.append(
                    "partition: the peer's settled cache counted no "
                    "replay hit for the retried request_id")
        ref = _reference_tokens(params, n_sessions, turns=3)
        res["token_identical"] = toks == ref
        if toks != ref:
            failures.append(
                "partition: continuations diverged from the "
                "uninterrupted run across partition + heal")
    except Exception as e:
        failures.append(f"partition: drill error: {type(e).__name__}: {e}")
    finally:
        faults.disarm()
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        shutil.rmtree(work, ignore_errors=True)
    return res


# ---- phase 4: burst shed (SLO-aware vs indiscriminate FIFO) -------------


def _burst(params, *, rate, seed, slo_aware: bool):
    """One open-loop burst at ``rate`` req/s, 25% priority traffic.
    ``slo_aware=False`` replays it with the pre-PR settings (even
    dequeue weights, one shared bound) for the BENCH contrast."""
    kw = (dict(class_weights=(4, 1), best_effort_queue_frac=0.5)
          if slo_aware else
          dict(class_weights=(1, 1), best_effort_queue_frac=1.0))
    srv = _build(params, 2, queue_size=16, **kw)
    with srv:
        srv.warmup(prompt_lens=(4,))
        report = run_loadgen(
            srv, vocab_size=_CFG.vocab_size, sessions=8,
            requests_per_session=8, prompt_len=4, max_new_tokens=8,
            mode="open", rate=rate, seed=seed, priority_frac=0.25,
            retry_max=1, retry_base_s=0.02, retry_cap_s=0.25,
        )
    return {
        "mode": "slo_aware" if slo_aware else "fifo",
        "offered_rate_rps": rate,
        "completed": report["completed"],
        "rejected": report["rejected"],
        "classes": report["classes"],
        "router": report["router"],
    }


def _phase_burst_shed(params, seed, slo_ms, failures):
    res = {"slo_ms": slo_ms}
    # calibrate sustainable throughput on the same stack shape
    cal_srv = _build(params, 2, queue_size=16)
    with cal_srv:
        cal_srv.warmup(prompt_lens=(4,))
        cal = run_loadgen(cal_srv, vocab_size=_CFG.vocab_size, sessions=4,
                          requests_per_session=4, prompt_len=4,
                          max_new_tokens=8, seed=seed)
    capacity = max(cal["requests_per_sec"], 1.0)
    rate = 4.0 * capacity
    res["capacity_rps"] = capacity
    res["burst_rate_rps"] = rate
    res["slo_aware"] = _burst(params, rate=rate, seed=seed, slo_aware=True)
    res["fifo"] = _burst(params, rate=rate, seed=seed + 1, slo_aware=False)
    pr = res["slo_aware"]["classes"]["priority"]
    be = res["slo_aware"]["classes"]["best_effort"]
    # "policy engaged" / "bound not inverted" read the ROUTER's per-class
    # shed counts (requests 429'd at admission), not the loadgen's gave-up
    # counter: whether a shed request's retries eventually land depends on
    # how fast the burst drains — a drain race on the calibrated rate —
    # while the admission bound rejecting best-effort (and only
    # best-effort) under a 4x burst is structural.
    ra = res["slo_aware"]["router"].get("shed_by_class", {})
    if ra.get("best_effort", 0) < 1:
        failures.append(
            "burst_shed: a 4x burst shed ZERO best-effort requests — "
            "the SLO-aware policy never engaged")
    if ra.get("priority", 0) > 0:
        failures.append(
            f"burst_shed: {ra.get('priority')} PRIORITY requests shed "
            "while best-effort headroom existed — the class bound is "
            "inverted")
    p99 = pr["p99_ttft_ms"]
    res["priority_p99_ttft_ms"] = p99
    res["best_effort_p99_ttft_ms"] = be["p99_ttft_ms"]
    if p99 is None or not p99 == p99 or p99 > slo_ms:
        failures.append(
            f"burst_shed: priority p99 TTFT {p99} ms missed the "
            f"{slo_ms} ms SLO under the 4x burst")
    res["retry_after_honored"] = (
        be["retried"] >= 1 and ra.get("best_effort", 0) >= 1)
    if be["retried"] < 1:
        failures.append(
            "burst_shed: the loadgen client never retried a shed — "
            "Retry-After honoring is untested by this run")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", type=str, default=None,
                    help="also write the machine-readable drill report "
                         "(the JSON line printed on stdout) here")
    ap.add_argument("--slo-ms", type=float, default=1000.0,
                    help="priority-class p99 TTFT SLO under the 4x burst "
                         "(CPU-noise-tolerant default)")
    ap.add_argument("--seed", type=int, default=0,
                    help="fault seed (reproduces the corruption bytes and "
                         "the workload)")
    args = ap.parse_args(argv)

    t_start = time.monotonic()
    params = init_lm(jax.random.PRNGKey(_SEED), _CFG)
    failures: list[str] = []
    summary = {"note": "chaos_serve", "seed": args.seed}
    summary["replica_death"] = _phase_replica_death(params, args.seed,
                                                    failures)
    summary["disk_faults"] = _phase_disk_faults(params, args.seed, failures)
    summary["latency_faults"] = _phase_latency_faults(params, args.seed,
                                                      failures)
    summary["burst_shed"] = _phase_burst_shed(params, args.seed,
                                              args.slo_ms, failures)
    summary["host_death"] = _phase_host_death(params, args.seed, failures)
    summary["partition"] = _phase_partition(params, args.seed, failures)
    summary["wall_s"] = round(time.monotonic() - t_start, 1)
    summary["result"] = "PASS" if not failures else "FAIL"
    summary["failures"] = failures
    print(json.dumps(summary))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
        print(f"chaos_serve: report written to {args.json}",
              file=sys.stderr)
    print(f"chaos_serve: {summary['result']} in {summary['wall_s']}s"
          + (f" — {len(failures)} violated invariant(s)" if failures
             else ""),
          file=sys.stderr)
    for f in failures:
        print(f"chaos_serve: FAIL {f}", file=sys.stderr)
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
