"""Checkpoint/resume: msgpack-serialized TrainState pytree + step counter.

Reference parity: SURVEY.md §5 "Checkpoint / resume" — believed ABSENT in the
reference (a driver crash loses the run); this is deliberate new capability,
and the fault-tolerance story for the rebuild: Spark's lineage-based task
retry has no XLA equivalent and is subsumed by checkpoint-restart
(SURVEY.md §7 step 6).

Multi-host safety (VERDICT r1 weak #6): on a multi-process run the params
can be sharded so no process holds the full arrays — ``jax.device_get``
would fail, and every process racing to write one file would corrupt it.
The multi-process path therefore writes ONE FILE PER PROCESS containing
only that process's addressable shards, deduplicated by ``replica_id == 0``
so each global index is written exactly once across the job; process 0
then writes a ``step_<N>.complete`` marker (only marked steps are
restorable — a crash mid-save never yields a half checkpoint). Restore
merges every process file, reassembles full host arrays, and reshards them
onto the template's shardings via ``make_array_from_callback``.

Durability + corruption story (the resilience plane's checkpoint half):
every state-bearing file is fsync'd before the rename that makes it
visible, carries a ``.sha256`` sidecar, and ``restore_latest`` verifies
before trusting — a checkpoint that fails its checksum (or cannot be
deserialized at all) is QUARANTINED (renamed ``*.quarantined``, kept for
forensics) and restore falls back to the newest valid step instead of
crashing the run. Provoked deterministically by the ``ckpt_corrupt`` fault
(resilience/faults.py) in tests/test_chaos*.py.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import threading

import jax
import numpy as np
from flax import serialization

from ..resilience import ckpt_layout as _ckpt_layout
from ..resilience import faults as _faults


class CorruptCheckpointError(RuntimeError):
    """A state-bearing file failed its sha256 sidecar check (torn write,
    bit rot, or an injected ``ckpt_corrupt`` drill). ``restore_latest``
    quarantines the offending step and falls back to the newest valid
    one; the serve disk tier (serve/state_cache.py) quarantines the
    session file and reports the state honestly lost; this type only
    escapes from paths with nothing to fall back to."""


def atomic_write(path: str, data: bytes, *, checksum: bool = False) -> None:
    """fsync + tmp-write + rename: partial writes never count, and the
    data is durable BEFORE the rename makes it visible (rename-first
    ordering can leave a zero-length "complete" file after power loss
    — exactly the torn state the restore path would then trust).
    ``checksum=True`` additionally writes a ``<path>.sha256`` sidecar
    (state-bearing files only) that :func:`read_verified` checks; any
    PREVIOUS sidecar is removed before the payload rename and the new one
    lands after it, so a crash anywhere in the sequence leaves a payload
    (old or new) without a sidecar — verified as legacy/unchecked, never
    as a false mismatch. This matters for OVERWRITTEN paths (best.msgpack,
    serve session files): without the pre-remove, a crash between the two
    renames would pair the new payload with the old file's hash and the
    reader would quarantine a perfectly valid file.

    The reusable durability core shared by training checkpoints and the
    serve session disk tier (serve/state_cache.py)."""
    # writer-unique tmp names: two processes/threads writing the SAME
    # path concurrently (e.g. serve replicas checkpointing one session
    # into a shared --session-dir during a retirement race) must not
    # interleave inside one tmp file — each writes its own and the
    # os.replace ordering decides, atomically, which full payload wins
    uniq = f".{os.getpid()}.{threading.get_ident()}.tmp"
    tmp = path + uniq
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    if checksum:
        try:  # never pair new bytes w/ old hash (no exists/remove
            os.remove(path + ".sha256")  # TOCTOU: a concurrent writer
        except FileNotFoundError:  # may have removed it first)
            pass
    os.replace(tmp, path)
    if checksum:
        side_tmp = path + ".sha256" + uniq
        with open(side_tmp, "wb") as f:
            f.write(hashlib.sha256(data).hexdigest().encode())
            f.flush()
            os.fsync(f.fileno())
        os.replace(side_tmp, path + ".sha256")


def read_verified(path: str) -> bytes:
    """Read a state-bearing file, checking its sha256 sidecar when one
    exists (pre-checksum files have none and are accepted). Raises
    :class:`CorruptCheckpointError` on mismatch."""
    with open(path, "rb") as f:
        data = f.read()
    side = path + ".sha256"
    try:
        with open(side) as f:
            expect = f.read().strip()
    except FileNotFoundError:
        # pre-checksum files have no sidecar — accepted as legacy. The
        # exists()-then-open TOCTOU this replaces could race a writer's
        # sidecar swap (atomic_write removes the old sidecar before the
        # payload rename) into a spurious "corrupt" verdict.
        return data
    got = hashlib.sha256(data).hexdigest()
    if got != expect:
        raise CorruptCheckpointError(
            f"{path}: sha256 mismatch (expected {expect[:12]}…, "
            f"got {got[:12]}…) — truncated or corrupted write"
        )
    return data


def _sync(name: str) -> None:
    """Cross-process barrier (no-op single-process)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


class Checkpointer:
    """Atomic msgpack checkpoints under ``directory``.

    Single-process: ``step_<N>.msgpack`` (whole state, unchanged format).
    Multi-process: ``step_<N>.proc<k>.msgpack`` per process + a
    ``step_<N>.complete`` marker from process 0.
    """

    # filename patterns live in resilience/ckpt_layout.py (the ONE naming
    # authority, jax-free so the supervisor can share it)
    _PAT = _ckpt_layout.STEP_PAT
    _PROC_PAT = _ckpt_layout.PROC_PAT
    _DONE_PAT = _ckpt_layout.DONE_PAT

    def __init__(self, directory: str, *, keep: int = 3,
                 async_save: bool = False):
        """``async_save``: overlap serialization + file IO with training.
        ``save()`` then blocks only for the device→host snapshot
        (`jax.device_get`) and hands the write to a background thread — at
        most one in flight (a second save waits for the first). Write
        errors surface at the next ``save()``/``wait()``; the interpreter
        joins the non-daemon writer at exit, so the last checkpoint is
        durable even without an explicit ``wait()``. Multi-process saves
        always run synchronously (their cross-process barriers belong on
        the main thread), whatever this flag says."""
        self.directory = directory
        self.keep = keep
        self.async_save = async_save
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None
        self._best_meta_cache: dict | None = None
        os.makedirs(directory, exist_ok=True)

    # -- discovery ---------------------------------------------------------

    def _steps(self) -> list[int]:
        """Restorable steps, ascending: single-file steps plus marked
        multi-process steps."""
        single, marked = set(), set()
        for name in os.listdir(self.directory):
            m = self._PAT.match(name)
            if m:
                single.add(int(m.group(1)))
            m = self._DONE_PAT.match(name)
            if m:
                marked.add(int(m.group(1)))
        return sorted(single | marked)

    def _files_for_step(self, step: int) -> list[str]:
        out = []
        for name in os.listdir(self.directory):
            for pat in (self._PAT, self._PROC_PAT, self._DONE_PAT):
                m = self._match_state_file(pat, name)
                if m and int(m.group(1)) == step:
                    out.append(os.path.join(self.directory, name))
        return out

    def has_checkpoint(self) -> bool:
        return bool(self._steps())

    def has_quarantined(self) -> bool:
        """True when the directory holds ``*.quarantined`` files — evidence
        that checkpoints EXISTED and were set aside as corrupt. A --resume
        that finds no valid checkpoint but sees this must refuse to fresh-
        start (cli._wire_checkpoint), or the supervisor's relaunch would
        silently defeat the corruption refusal one restart later."""
        try:
            return any(n.endswith(".quarantined")
                       for n in os.listdir(self.directory))
        except OSError:
            return False

    def latest_step(self):
        """Newest restorable step, or None."""
        steps = self._steps()
        return steps[-1] if steps else None

    def fence_after(self, step: int) -> None:
        """Delete every step_N checkpoint NEWER than ``step`` — the
        --resume-best rewind: the abandoned lineage's later checkpoints
        must not be restorable, or a subsequent --resume would silently
        continue the diverged weights the user rewound away from.

        Multi-process: process 0 deletes, everyone barriers on both
        edges — concurrent unlinks of the same shared-fs files would
        race, and no process may proceed to re-save until the fence is
        fully down."""
        _sync(f"ckpt_fence_enter_{step}")
        if jax.process_index() == 0:
            for s in self._steps():
                if s > step:
                    for f in self._files_for_step(s):
                        os.remove(f)
        _sync(f"ckpt_fence_done_{step}")

    # -- save --------------------------------------------------------------

    def save(self, state) -> str:
        from ..utils import span

        with span("checkpoint_save"):
            if jax.process_count() > 1:
                path = self._save_sharded(state)
                self._cleanup()
            elif self.async_save:
                self.wait()  # one write in flight; surface prior errors
                host = jax.device_get(state)  # snapshot BEFORE training moves on
                path = self._path_for(int(host.step))
                self._thread = threading.Thread(
                    target=self._write_and_clean, args=(host,),
                    name="checkpoint-writer",
                )
                self._thread.start()
            else:
                path = self._save_single(jax.device_get(state))
                self._cleanup()
        return path

    def wait(self) -> None:
        """Join any in-flight async write; re-raise its error if it failed."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _write_and_clean(self, host_state) -> None:
        try:
            self._save_single(host_state)
            self._cleanup()
        except BaseException as e:  # surfaced by the next save()/wait()
            self._error = e

    def _cleanup(self) -> None:
        # keep-N, oldest first (process 0 only — the others' files are
        # deleted by step, after the save barrier)
        if jax.process_index() == 0:
            for step in self._steps()[: -self.keep]:
                for f in self._files_for_step(step):
                    os.remove(f)

    def _path_for(self, step: int) -> str:
        """Single-process checkpoint filename — the ONE naming authority
        (must stay in sync with ``_PAT``)."""
        return os.path.join(self.directory, f"step_{step}.msgpack")

    # -- best-metric checkpoint -------------------------------------------

    @property
    def _best_path(self) -> str:
        return os.path.join(self.directory, "best.msgpack")

    def save_best(self, state, value: float) -> str:
        """Write/overwrite the best-eval checkpoint.

        Single-process: ONE atomic artifact (``best.msgpack``: {step,
        value, state-bytes}) so the metadata can never describe different
        weights than the file holds. Multi-process (VERDICT r3 item 7):
        the SAME sharded-writer machinery as ``save()`` — one
        ``best_<step>.proc<k>.msgpack`` per process, then a
        ``best.complete`` marker from process 0 carrying {writers, step,
        value}; the step-stamped filenames mean a crash mid-save can
        never mix old and new shard files under one marker (the old
        marker keeps pointing at the old, complete set). ``best.json``
        is a derived convenience view written after (advisory only).
        Called by the train loop only on metric improvement, so it stays
        synchronous (rare) and independent of the step_N rotation —
        keep-N cleanup never deletes it."""
        if jax.process_count() > 1:
            return self._save_best_sharded(state, value)
        self.wait()  # never interleave with an in-flight async write
        host = jax.device_get(state)
        payload = {
            "step": int(host.step),
            "value": float(value),
            "state": serialization.to_bytes(host),
        }
        self._atomic_write(self._best_path,
                           serialization.msgpack_serialize(payload),
                           checksum=True)
        meta = os.path.join(self.directory, "best.json")
        self._atomic_write(
            meta,
            json.dumps({"step": payload["step"],
                        "value": payload["value"]}).encode(),
        )
        # a single-process best supersedes any earlier SHARDED best: drop
        # its marker + shard files so the two artifact kinds never coexist
        # past a save (see _best_artifact for the crash-window tiebreak)
        try:
            os.remove(self._best_marker)
        except FileNotFoundError:
            pass  # no sharded best to supersede
        for name in os.listdir(self.directory):
            if self._match_state_file(self._BEST_PROC_PAT, name):
                os.remove(os.path.join(self.directory, name))
        self._best_meta_cache = {"step": payload["step"],
                                 "value": payload["value"]}
        return self._best_path

    @staticmethod
    def _match_state_file(pat, name: str):
        """Pattern-match a state filename OR its ``.sha256`` sidecar (the
        sidecar shares every lifecycle event — cleanup, fencing,
        quarantine — with its payload)."""
        base = name[: -len(".sha256")] if name.endswith(".sha256") else name
        return pat.match(base)

    _BEST_PROC_PAT = re.compile(r"best_(\d+)\.proc(\d+)\.msgpack$")

    @property
    def _best_marker(self) -> str:
        return os.path.join(self.directory, "best.complete")

    def _save_best_sharded(self, state, value: float) -> str:
        # defensive fence for save-path symmetry with save_best (ADVICE
        # r4): today async step saves only start on the single-process
        # branch, so this is a no-op under current routing — it exists so
        # the "never interleave with an in-flight async write" contract
        # survives if a sharded async path is ever added (wait() also
        # re-raises a failed writer's exception, same as save_best)
        self.wait()
        step = int(jax.device_get(state.step))
        pid = jax.process_index()
        # clear leftovers of a crashed attempt AT THIS STEP (other steps'
        # files may be the live best — only the marker says which)
        if pid == 0:
            for name in os.listdir(self.directory):
                m = self._match_state_file(self._BEST_PROC_PAT, name)
                if m and int(m.group(1)) == step:
                    os.remove(os.path.join(self.directory, name))
        _sync(f"best_clean_{step}")
        payload = self._local_shards_payload(state, step)
        payload["value"] = float(value)
        path = os.path.join(self.directory,
                            f"best_{step}.proc{pid}.msgpack")
        self._atomic_write(path, serialization.msgpack_serialize(payload),
                           checksum=True)
        # every process must finish before the marker flips the live best
        _sync(f"best_save_{step}")
        if pid == 0:
            meta = {"writers": jax.process_count(), "step": step,
                    "value": float(value)}
            self._atomic_write(self._best_marker,
                               json.dumps(meta).encode())
            self._atomic_write(
                os.path.join(self.directory, "best.json"),
                json.dumps({"step": step, "value": float(value)}).encode(),
            )
            # the marker now points at this step's set: older sets AND any
            # single-process best.msgpack from an earlier 1-process run are
            # dead (a stale best.msgpack must not shadow this best)
            for name in os.listdir(self.directory):
                m = self._match_state_file(self._BEST_PROC_PAT, name)
                if m and int(m.group(1)) != step:
                    os.remove(os.path.join(self.directory, name))
            for stale in (self._best_path, self._best_path + ".sha256"):
                try:
                    os.remove(stale)
                except FileNotFoundError:
                    pass  # never existed (or a peer already removed it)
        _sync(f"best_done_{step}")
        self._best_meta_cache = {"step": step, "value": float(value)}
        return path

    def _best_artifact(self):
        """(kind, meta, payload) of the live best artifact, or
        (None, None, None). ``payload`` is the already-verified, parsed
        best.msgpack content for the "single" kind (None for "sharded") —
        returned so restore_best never re-reads and re-hashes a
        potentially multi-GB file the arbitration below just processed.

        Each save deletes the OTHER kind, so both coexist only in the
        tiny crash window between writing the new artifact and unlinking
        the old — arbitrate by step, newer wins (tie → the single-file
        artifact: it is self-contained). Without the tiebreak a stale
        best.msgpack from an earlier 1-process run would permanently
        shadow every later sharded best."""
        single = sharded = None
        payload = None
        if os.path.exists(self._best_path):
            # OSError propagates: transient IO is not corruption — retry,
            # don't destroy discoverability (same policy as restore_latest)
            try:
                payload = self._classified_parse(
                    self._best_path, self._read_verified(self._best_path),
                    serialization.msgpack_restore)
                single = {"step": int(payload["step"]),
                          "value": float(payload["value"])}
            except CorruptCheckpointError as e:
                # a CORRUPT best must not crash best_meta/restore_best (or
                # shadow a valid sharded best): set it aside and move on
                print(f"checkpoint: QUARANTINING corrupt best.msgpack: "
                      f"{e}", flush=True)
                for p in (self._best_path, self._best_path + ".sha256"):
                    try:
                        os.replace(p, p + ".quarantined")
                    except OSError:
                        pass  # best effort; discovery will retry it
        try:
            with open(self._best_marker) as f:
                meta = json.loads(f.read())
        except FileNotFoundError:
            # no sharded best (the common single-process layout); the
            # exists()-then-open this replaces could race a concurrent
            # save_best's marker removal into a crash
            pass
        else:
            sharded = {"step": int(meta["step"]),
                       "value": float(meta["value"]),
                       "writers": int(meta["writers"])}
        if single is not None and (sharded is None
                                   or sharded["step"] <= single["step"]):
            return "single", single, payload
        if sharded is not None:
            return "sharded", sharded, None
        return None, None, None

    def best_meta(self) -> dict | None:
        """{step, value} of the saved best checkpoint (from the
        AUTHORITATIVE artifact, not the advisory sidecar; cached after the
        first read — the state-bearing file is parsed once, not once per
        caller), or None. Used to seed the train loop's best-so-far across
        restarts so a resumed run can never overwrite a better best with a
        worse one."""
        if self._best_meta_cache is not None:
            return dict(self._best_meta_cache)
        self.wait()
        kind, meta, _ = self._best_artifact()
        if kind is None:
            return None
        self._best_meta_cache = {"step": meta["step"], "value": meta["value"]}
        return dict(self._best_meta_cache)

    def restore_best(self, template):
        """Restore the best-metric checkpoint (None if never saved).
        Handles both artifact kinds: the single-process ``best.msgpack``
        and the sharded ``best_<step>.proc<k>`` set named by
        ``best.complete`` — a sharded best restores (resharded onto the
        template) even under a LATER different process count, like any
        sharded step checkpoint."""
        self.wait()
        kind, meta, payload = self._best_artifact()
        if kind is None:
            return None
        if kind == "single":
            # payload was read, verified and parsed by _best_artifact —
            # no second multi-GB read/hash of the same file
            restored = serialization.from_bytes(template, payload["state"])
            return self._reshard_like(template, restored)
        step, writers = meta["step"], meta["writers"]
        paths = []
        for name in sorted(os.listdir(self.directory)):
            m = self._BEST_PROC_PAT.match(name)
            if m and int(m.group(1)) == step and int(m.group(2)) < writers:
                paths.append(os.path.join(self.directory, name))
        try:
            if len(paths) < writers:
                raise CorruptCheckpointError(
                    f"best step {step}: only {len(paths)} of {writers} "
                    "proc files present"
                )
            return self._assemble_from_procs(template, paths, step)
        except CorruptCheckpointError as e:
            # same contract as the single-file best and restore_latest:
            # corruption quarantines the artifact and reports "no best"
            # instead of crashing a --resume-best run
            print(f"checkpoint: QUARANTINING corrupt sharded best "
                  f"(step {step}): {e}", flush=True)
            for p in [*paths, *(p + ".sha256" for p in paths),
                      self._best_marker]:
                try:
                    os.replace(p, p + ".quarantined")
                except OSError:
                    pass  # already gone (or a peer quarantined it first)
            self._best_meta_cache = None
            return None

    # module-level atomic_write/read_verified (extracted so the serve
    # session disk tier shares the exact durability core), bound as
    # staticmethods to keep every existing call site working
    _atomic_write = staticmethod(atomic_write)

    @staticmethod
    def _classified_parse(path: str, data: bytes, parse):
        """Parse state ``data`` read from ``path``, classifying failure by
        the module's ONE corruption policy: checksum-VERIFIED bytes that
        fail to parse mean a structural/config mismatch (re-raised loudly
        — quarantining would destroy valid checkpoints), while a legacy
        unchecksummed file that fails to parse is indistinguishable from
        truncation (→ CorruptCheckpointError, favoring recovery)."""
        try:
            return parse(data)
        except Exception as e:
            if os.path.exists(path + ".sha256"):
                raise  # verified bytes: not corruption — surface it
            raise CorruptCheckpointError(
                f"{path}: cannot deserialize legacy (unchecksummed) file: "
                f"{type(e).__name__}: {e}"
            ) from e

    _read_verified = staticmethod(read_verified)

    def _quarantine_step(self, step: int, reason: str) -> None:
        """Rename every file of a corrupt step to ``*.quarantined`` so the
        discovery patterns stop matching it (restore falls back to the
        next-newest step) while the evidence stays on disk for forensics.
        Quarantined files are exempt from keep-N cleanup."""
        print(f"checkpoint: QUARANTINING step {step}: {reason}", flush=True)
        for f in self._files_for_step(step):
            try:
                os.replace(f, f + ".quarantined")
            except OSError:
                pass  # best effort: a vanished file is already "gone"

    def _save_single(self, host_state) -> str:
        step = int(host_state.step)
        path = self._path_for(step)
        self._atomic_write(path, serialization.to_bytes(host_state),
                           checksum=True)
        # chaos drills: an armed ckpt_corrupt fault tears THIS file now,
        # after the write completed — the restore-side checksum must catch
        # it and fall back (tests/test_chaos*.py)
        _faults.maybe_corrupt_checkpoint(path, step)
        return path

    def _local_shards_payload(self, state, step: int) -> dict:
        """This process's contribution to a sharded checkpoint: for each
        leaf, the addressable shards it uniquely owns (``replica_id == 0``
        dedupe — exactly one writer per global index across the job);
        host-side leaves belong to process 0. Shared by the step and best
        sharded writers."""
        pid = jax.process_index()
        leaves = jax.tree.leaves(state)
        payload: dict = {"step": step, "leaves": {}}
        for i, leaf in enumerate(leaves):
            recs = []
            if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
                for sh in leaf.addressable_shards:
                    if sh.replica_id != 0:
                        continue  # exactly one writer per global index
                    idx = sh.index  # tuple of slices into the global shape
                    recs.append({
                        "start": [int(s.start or 0) for s in idx],
                        "stop": [
                            int(s.stop if s.stop is not None else d)
                            for s, d in zip(idx, leaf.shape)
                        ],
                        "data": np.asarray(sh.data),
                    })
            else:  # host-side leaf: process 0 owns it
                if pid == 0:
                    a = np.asarray(leaf)
                    recs.append({
                        "start": [0] * a.ndim,
                        "stop": list(a.shape),
                        "data": a,
                    })
            if recs:
                payload["leaves"][str(i)] = recs
        return payload

    def _save_sharded(self, state) -> str:
        # state.step is replicated → locally readable on every process
        step = int(jax.device_get(state.step))
        pid = jax.process_index()
        # Clear any leftovers for this step from a previously crashed save
        # (possibly with a DIFFERENT process count): stale proc files would
        # otherwise merge into a later restore and corrupt it.
        if pid == 0:
            for f in self._files_for_step(step):
                os.remove(f)
        _sync(f"ckpt_clean_{step}")
        payload = self._local_shards_payload(state, step)
        path = os.path.join(self.directory, f"step_{step}.proc{pid}.msgpack")
        self._atomic_write(path, serialization.msgpack_serialize(payload),
                           checksum=True)
        # chaos drills fire on sharded saves too (process 0 tears its own
        # proc file — deterministic, no cross-process marker race), so a
        # multi-process ckpt_corrupt drill exercises _restore_sharded's
        # corruption fallback instead of silently never firing
        if pid == 0:
            _faults.maybe_corrupt_checkpoint(path, step)
        # every process must finish writing before the step is marked
        # restorable (assumes a shared filesystem, the standard pod setup)
        _sync(f"ckpt_save_{step}")
        if pid == 0:
            done = os.path.join(self.directory, f"step_{step}.complete")
            # marker records the writer count: restore only merges proc
            # files below it (second guard against stale files)
            self._atomic_write(done, str(jax.process_count()).encode())
        _sync(f"ckpt_done_{step}")
        return path

    # -- restore -----------------------------------------------------------

    def restore_latest(self, template):
        """Restore the newest checkpoint into the structure of ``template``
        (same model/optimizer config); None if no checkpoint exists.

        Template leaves that are sharded jax.Arrays get the restored values
        RESHARDED onto their shardings (works across a changed process
        count / mesh layout); host leaves come back as host arrays.

        Corruption fallback: a newest checkpoint that is CORRUPT — checksum
        mismatch, an undeserializable legacy (unchecksummed) file, or a
        sharded step missing proc files — is QUARANTINED (files renamed
        ``*.quarantined``, kept for forensics) and the next-newest step is
        tried, until a valid one restores or none remain (→ None, with a
        loud warning per quarantined step). A truncated write must cost
        one checkpoint interval, not the run. Deliberately NOT swallowed:
        OSError (transient IO is not corruption — retry, don't destroy
        discoverability) and deserialization failures of checksum-VERIFIED
        files (bytes are exactly what was written, so the template/model
        config is wrong — quarantining every checkpoint would silently
        restart the run from step 0). Each step is attempted at most once
        per call, so a quarantine that cannot rename (read-only dir) still
        terminates.
        """
        self.wait()  # never read around an in-flight write
        attempted: set[int] = set()
        while True:
            steps = [s for s in self._steps() if s not in attempted]
            if not steps:
                return None
            step = steps[-1]
            attempted.add(step)
            single = self._path_for(step)
            try:
                if os.path.exists(single):
                    restored = self._deserialize_verified(template, single)
                    return self._reshard_like(template, restored)
                return self._restore_sharded(template, step)
            except CorruptCheckpointError as e:
                self._quarantine_step(step, str(e))
            except FileNotFoundError as e:
                # NOT the transient-IO class: a file that existed at
                # discovery and is gone at read was quarantined by a PEER
                # process racing the same corrupt step (multi-process
                # restore). Fall back like the peer did, so every process
                # converges on the same older step and the sync barriers
                # stay aligned.
                self._quarantine_step(step, f"vanished mid-read "
                                            f"(peer quarantine?): {e}")

    def restore_latest_params(self, params):
        """The newest restorable checkpoint's ``params`` ALONE, into the
        structure of ``params`` — whatever optimizer, clipping, schedule
        or recurrent carries the producing run had. Serving and
        distillation need the weights and must not have to re-state the
        training flags to get them (a template built from `--optimizer`
        alone cannot match a run that also clipped). None when nothing
        restores (same fallback/quarantine policy as `restore_latest`).

        A template field left None restores to nothing: the single-file
        format hands back its raw sub-dict, which is dropped here; the
        sharded format numbers leaves in `TrainState` order, where
        ``step`` and ``params`` come first, so their numbers hold."""
        from .loop import TrainState

        state = self.restore_latest(TrainState(
            step=np.zeros((), np.int32), params=params, opt_state=None,
            rng=None))
        return None if state is None else state.params

    def _deserialize_verified(self, template, path: str):
        """Checksum-check then deserialize one single-file checkpoint,
        classifying failures: checksum mismatch → CorruptCheckpointError
        (quarantine + fall back); parse failure of a VERIFIED file →
        re-raised as-is (the bytes are intact, so the template/config is
        wrong — a loud error, not a quarantine); parse failure of a legacy
        unchecksummed file → CorruptCheckpointError (truncation and
        mismatch are indistinguishable there — favor recovery)."""
        data = self._read_verified(path)  # raises CorruptCheckpointError
        return self._classified_parse(
            path, data, lambda d: serialization.from_bytes(template, d))

    def _restore_sharded(self, template, step: int):
        done = os.path.join(self.directory, f"step_{step}.complete")
        try:
            with open(done) as f:
                n_writers = int(f.read().strip() or 0)
        except (OSError, ValueError):
            n_writers = None  # legacy "ok" marker: accept all proc files
        paths = []
        for name in sorted(os.listdir(self.directory)):
            m = self._PROC_PAT.match(name)
            if not m or int(m.group(1)) != step:
                continue
            if n_writers is not None and int(m.group(2)) >= n_writers:
                continue  # stale file from an older, larger job
            paths.append(os.path.join(self.directory, name))
        if n_writers is not None and len(paths) < n_writers:
            # a marked-complete step with vanished proc files is damage,
            # not a config problem: fall back like any other corruption
            raise CorruptCheckpointError(
                f"checkpoint step {step}: only {len(paths)} of {n_writers} "
                "proc files present"
            )
        return self._assemble_from_procs(template, paths, step)

    def _assemble_from_procs(self, template, paths: list, step: int):
        """Merge per-process shard files and reassemble every template
        leaf, resharding onto the template's shardings (shared by the
        step and best restore paths)."""
        merged: dict[int, list] = {}
        for p in paths:
            payload = self._classified_parse(
                p, self._read_verified(p), serialization.msgpack_restore)
            for k, recs in payload["leaves"].items():
                merged.setdefault(int(k), []).extend(recs)
        t_leaves, treedef = jax.tree.flatten(template)
        out = []
        # Assemble + place ONE LEAF AT A TIME so peak host memory is the
        # largest single leaf, not the whole model. (Each process still
        # materializes the full leaf before resharding — acceptable until a
        # single leaf outgrows host RAM.)
        for i, t in enumerate(t_leaves):
            recs = merged.pop(i, None)
            if not recs:
                raise ValueError(
                    f"checkpoint step {step} is missing leaf {i}; "
                    "was it written with a different model config?"
                )
            shape = tuple(np.asarray(t).shape) if not isinstance(t, jax.Array) \
                else t.shape
            full = np.empty(shape, dtype=np.asarray(recs[0]["data"]).dtype)
            for r in recs:
                idx = tuple(
                    slice(int(a), int(b)) for a, b in zip(r["start"], r["stop"])
                )
                full[idx] = r["data"]
            out.append(self._place_leaf(t, full))
            del full, recs
        return jax.tree.unflatten(treedef, out)

    @staticmethod
    def _place_leaf(t, v):
        """Place one restored host value onto its template leaf's sharding.

        Reshards only onto MULTI-device template shardings. Leaves whose
        template is host-side or single-device stay as host numpy —
        committing them (e.g. the step scalar) to one local device would
        conflict with the global arrays at the next jit call."""
        if (
            isinstance(t, jax.Array)
            and hasattr(t, "sharding")
            and getattr(t.sharding, "num_devices", 1) > 1
            and not isinstance(v, jax.Array)
        ):
            host = np.asarray(v)
            return jax.make_array_from_callback(
                host.shape, t.sharding, lambda idx: host[idx]
            )
        return v

    def _reshard_like(self, template, restored):
        # a None in the template (restore_latest_params) takes whatever
        # the file held there, unplaced
        return jax.tree.map(self._place_leaf, template, restored,
                            is_leaf=lambda t: t is None)
