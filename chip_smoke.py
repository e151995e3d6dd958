#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py              # one TPU chip, roughly ten minutes cold
    python3 chip_smoke.py --multichip  # four chips: DP training + 4 replicas

Drives the main path once through the entry points a user would call, at
the full width of BASELINE config 5 (4x1024 LSTM LM, V=50,000; weights
random from --seed, the corpus generated from --seed):

  device          jax.devices()[0].platform must be "tpu" — else stop here
  train           `main.py --dataset wikitext103` with configs/wikitext103_dp.sh's
                  flags at the one-chip shape (B=32, T=64, --use-pallas, fused
                  backward): V must be 50,000, the loss finite and falling,
                  the Pallas recurrence engaged
  serve_selftest  `cli serve --selftest` on the checkpoint `train` wrote
  serve_http      `cli serve --http` on the same weights: /healthz, a few
                  /v1/generate (one continued session), /stats with zero
                  compiles after warm-up
  small_train     `main.py --dataset ptb_char --use-pallas` (README quick start)
  small_selftest  `cli serve --selftest` at its default widths, where `auto`
                  resolves to the compiled Pallas decode window

With --multichip, ONLY: config 5 data-parallel over four chips against the
one-chip step on the same global batch, and `cli serve --replicas 4`.

One process holds the chip at a time, so this parent never imports JAX:
every phase is a child process (this file, `--phase NAME`), run one after
the other, and each reports the device it ran on. stdout carries one JSON
object per phase and, last, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as the children reported it. Any phase that raises, fails
its checks or times out makes the exit code non-zero and the last line
`"ok": false`. There is no option that lets it pass without a TPU; the CPU
rehearsal of these phases, at tiny sizes, is tests/test_chip_smoke.py. No
compile-cache path is set here: utils/compile_cache.py places it.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import glob
import io
import json
import os
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

PHASES = ("train", "serve_selftest", "serve_http", "small_train",
          "small_selftest")
MULTICHIP_PHASES = ("dp_train", "replicas")
#: per-child wall-clock bound; the whole one-chip run stays inside 1200 s
PHASE_TIMEOUT_S = 600


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run is sized to. FULL is the only size `main()` uses; the
    tests rehearse the same phases at a tiny one."""
    vocab: int          # the model vocabulary `train` must report
    word_types: int     # distinct words in the generated corpus (> vocab)
    train_tokens: int
    hidden: int
    layers: int
    batch: int
    seq_len: int
    calls: int          # train dispatches, 4 optimizer steps each
    small_steps_per_call: int  # the ptb_char quick-start run's dispatch
    # serve lattice: a deployment setting — every (bucket x bucket) pair
    # is one compile, and at 4x1024/V=50k a compile is the cost
    prefill_buckets: str
    batch_buckets: str

    @property
    def model_flags(self) -> list[str]:
        return ["--hidden-units", str(self.hidden),
                "--num-layers", str(self.layers),
                "--compute-dtype", "bfloat16"]

    @property
    def serve_flags(self) -> list[str]:
        return ["--vocab-size", str(self.vocab), *self.model_flags,
                "--prefill-buckets", self.prefill_buckets,
                "--batch-buckets", self.batch_buckets,
                "--num-slots", "32", "--max-active", "8"]


FULL = Sizes(vocab=50_000, word_types=60_000, train_tokens=400_000,
             hidden=1024, layers=4, batch=32, seq_len=64, calls=3,
             small_steps_per_call=64,
             prefill_buckets="16,64", batch_buckets="1,8")


#: the jitted train steps (train/loop.py, multistep.py, device_step.py): a
#: training run must hand XLA its step exactly once
TRAIN_STEPS = {f"jit({name})" for name in (
    "core", "per_shard", "step", "multi_step", "train_step")}


class CheckFailed(Exception):
    """A phase ran to its end and what came out is wrong."""


def require(cond, message: str) -> None:
    if not cond:
        raise CheckFailed(message)


# ---------------------------------------------------------------------------
# children: each runs ONE phase in its own process and owns the chip
# ---------------------------------------------------------------------------


def device_report() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes() -> list:
    """`peak_bytes_in_use` of every device (None where the backend does
    not report it — the CPU)."""
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


class Observed:
    """What JAX compiled while one phase ran: seconds XLA spent compiling
    or fetching from the persistent cache (JAX's own monitoring events),
    and every program it lowered, as StableHLO text under ``directory`` —
    written at lowering time, so a compile-cache hit still leaves the text
    to count kernels in."""

    def __init__(self, directory: str):
        self.directory = directory
        self.seconds = 0.0
        self.cache_hits = 0
        self.by_name: dict[str, list[float]] = {}  # program -> seconds

    def _duration(self, name, secs, fun_name="?", **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.by_name.setdefault(fun_name, []).append(secs)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring

        os.makedirs(self.directory, exist_ok=True)
        jax.config.update("jax_dump_ir_to", self.directory)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        jax.config.update("jax_dump_ir_to", "")

    def lowered(self) -> list[str]:
        texts = []
        for path in glob.glob(os.path.join(self.directory, "*")):
            with open(path, errors="replace") as f:
                texts.append(f.read())
        return texts

    def report(self) -> dict:
        """``tpu_custom_calls``: the most Mosaic kernels any ONE lowered
        program of the phase holds (the train step, the decode window)."""
        return {"compile_s": round(self.seconds, 2),
                "programs_compiled": sum(map(len, self.by_name.values())),
                "compile_cache_hits": self.cache_hits,
                # a train step XLA was handed more than once in ONE run is
                # a recompile somebody pays for at every launch
                "train_step_compiles": {
                    n: len(secs) for n, secs in self.by_name.items()
                    if n in TRAIN_STEPS},
                "tpu_custom_calls": max(
                    [t.count("tpu_custom_call") for t in self.lowered()],
                    default=0),
                "peak_bytes_in_use": peak_bytes()}


def run_cli(argv: list[str]) -> tuple[int, str]:
    """The normal entry point (`python main.py ...` / `python -m
    lstm_tensorspark_tpu.cli ...`), in this process; ``(rc, stdout)``
    with stdout still echoed."""
    from lstm_tensorspark_tpu.cli import main

    class Tee(io.StringIO):
        def write(self, s):
            sys.__stdout__.write(s)
            return super().write(s)

    out = Tee()
    with contextlib.redirect_stdout(out):
        try:
            rc = main(argv)
        except SystemExit as e:
            rc = e.code if isinstance(e.code, int) else 1
            if not isinstance(e.code, int) and e.code is not None:
                print(e.code)
    return rc, out.getvalue()


def last_json(text: str, note: str) -> dict:
    for line in reversed(text.splitlines()):
        if line.startswith("{") and f'"note": "{note}"' in line:
            return json.loads(line)
    raise CheckFailed(f"no {note!r} record in the output")


def train_argv(workdir: str, sizes: Sizes, seed: int, *, jsonl: str,
               extra: list[str]) -> list[str]:
    """configs/wikitext103_dp.sh, at a one-chip shape (B=32, T=64),
    without --remat-chunk so the fused backward kernel runs as well as
    the forward, in the dispatch form README.md uses (--device-data
    --steps-per-call)."""
    return [
        "--dataset", "wikitext103", *sizes.model_flags,
        "--batch-size", str(sizes.batch), "--seq-len", str(sizes.seq_len),
        "--optimizer", "adam", "--learning-rate", "1e-3",
        "--clip-norm", "1.0", "--dropout", "0.2", "--stateful",
        "--logits-dtype", "bfloat16", "--use-pallas",
        "--device-data", "--steps-per-call", "4",
        "--num-steps", str(4 * sizes.calls), "--log-every", "1",
        "--eval-batches", "2", "--seed", str(seed),
        "--data-path", os.path.join(workdir, "corpus"),
        "--jsonl", jsonl, *extra,
    ]


def train_facts(jsonl: str) -> dict:
    """What a training run's JSONL says happened."""
    with open(jsonl) as f:
        records = [json.loads(line) for line in f]
    start = next(r for r in records if r.get("note") == "start")
    steps = [r for r in records if "loss" in r and "step" in r
             and "note" not in r]
    final = next((r for r in records if r.get("note") == "final"), {})
    traced = next((r["recurrence_traced"] for r in records
                   if "recurrence_traced" in r), [])
    facts = {
        "vocab": start["vocab"], "backend": start["backend"],
        "partitions": start["partitions"], "state_on": start["state_on"],
        "recurrence": start["recurrence"], "recurrence_traced": traced,
        "losses": [r["loss"] for r in steps],
        "eval_loss": final.get("eval_loss"),
    }
    if len(steps) >= 2:
        # the first dispatch carries the compile; the rest should be steady
        # (a host clock around so few dispatches is a reading, not a rate)
        per_step = [(b["t"] - a["t"]) / (b["step"] - a["step"])
                    for a, b in zip(steps, steps[1:])]
        facts["step_s"] = statistics.median(per_step)
        facts["step_s_readings"] = per_step
        facts["first_dispatch_s"] = round(steps[0]["t"] - start["t"], 2)
    return facts


def check_train(facts: dict, *, layers_fused: int,
                vocab: int | None = None) -> None:
    import math

    losses = facts["losses"]
    require(vocab is None or facts["vocab"] == vocab,
            f"vocab={facts['vocab']}, expected {vocab}")
    require(len(losses) >= 2, f"fewer than two dispatches logged: {losses}")
    require(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require(facts["eval_loss"] is not None
            and math.isfinite(facts["eval_loss"]), "no finite final eval loss")
    note = facts["recurrence"]
    require(note.startswith("pallas fwd=") and "recompute" not in note,
            f"the recurrence is not the fused Pallas pair: {note}")
    traced = facts["recurrence_traced"]
    require(note in traced and all(t.startswith("pallas") for t in traced),
            f"traced {traced}, start record said {note}")
    require(set(facts["train_step_compiles"].values()) == {1},
            f"train step compiles: {facts['train_step_compiles']}")
    # forward + backward kernel per layer in the lowered train program
    require(facts["tpu_custom_calls"] >= 2 * layers_fused,
            f"{facts['tpu_custom_calls']} tpu_custom_call in the lowered "
            f"train step, expected >= {2 * layers_fused}")


def phase_train(workdir: str, sizes: Sizes, seed: int):
    jsonl = os.path.join(workdir, "train.jsonl")
    with Observed(os.path.join(workdir, "ir_train")) as seen:
        rc, _ = run_cli(train_argv(
            workdir, sizes, seed, jsonl=jsonl,
            extra=["--checkpoint-dir", os.path.join(workdir, "ckpt"),
                   "--checkpoint-every", str(sizes.calls)]))
    require(rc == 0, f"main.py exited {rc}")
    result = {"hidden": sizes.hidden, "layers": sizes.layers,
              **train_facts(jsonl), **seen.report()}
    return result, lambda: check_train(result, layers_fused=sizes.layers,
                                       vocab=sizes.vocab)


def selftest(workdir: str, tag: str, argv: list[str], *, kernel: str):
    t0 = time.monotonic()
    with Observed(os.path.join(workdir, f"ir_{tag}")) as seen:
        rc, out = run_cli(["serve", "--selftest", *argv])
    rec = last_json(out, "serve_selftest")
    keep = ("sessions", "tokens_per_session", "mismatches", "mismatches_tied",
            "decode_kernel", "compiles_prefill", "compiles_decode",
            "compiles_decode_window", "compiles_decode_window_pallas",
            "decode_window_scan_fallbacks", "replicas", "completed",
            "failed", "windows_dispatched")
    result = {k: rec[k] for k in keep}
    result.update(rc=rc, seconds=round(time.monotonic() - t0, 2),
                  judged=[l for l in out.splitlines() if "MISMATCH" in l],
                  **seen.report())

    def check():
        r = result
        require(rc == 0, f"selftest exited {rc}")
        require(r["decode_kernel"] == kernel,
                f"decode kernel resolved to {r['decode_kernel']}, "
                f"expected {kernel}")
        require(r["mismatches"] == r["mismatches_tied"],
                f"{r['mismatches'] - r['mismatches_tied']} real "
                "mismatch(es) against models/generate.py")
        require(r["completed"] == r["sessions"] and r["failed"] == 0,
                f"{r['completed']}/{r['sessions']} sessions completed")
        if kernel == "pallas":
            require(r["compiles_decode_window_pallas"] > 0
                    and r["decode_window_scan_fallbacks"] == 0
                    and r["tpu_custom_calls"] >= 1,
                    "the Pallas decode window did not run compiled: "
                    f"{r['compiles_decode_window_pallas']} programs, "
                    f"{r['decode_window_scan_fallbacks']} scan fallbacks, "
                    f"{r['tpu_custom_calls']} tpu_custom_call")

    return result, check


def phase_serve_selftest(workdir: str, sizes: Sizes, seed: int):
    """The same weights `train` wrote. At V=50,000 the fused decode
    window's VMEM plan cannot hold the embedding, so `auto` is the scan
    window here; greedy mismatches are judged by the selftest itself
    against the float32 reference (ties counted, never hidden)."""
    return selftest(workdir, "serve_selftest", [
        "--checkpoint-dir", os.path.join(workdir, "ckpt"),
        "--seed", str(seed), *sizes.serve_flags], kernel="scan")


def phase_small_selftest(workdir: str, sizes: Sizes, seed: int):
    """`cli serve --selftest` exactly as a user types it: at the default
    widths `auto` resolves to the Pallas decode window on a TPU."""
    return selftest(workdir, "small_selftest", ["--seed", str(seed)],
                    kernel="pallas")


def phase_small_train(workdir: str, sizes: Sizes, seed: int):
    """README.md's fastest single-chip configuration, three dispatches."""
    jsonl = os.path.join(workdir, "small_train.jsonl")
    k = sizes.small_steps_per_call
    with Observed(os.path.join(workdir, "ir_small_train")) as seen:
        rc, _ = run_cli([
            "--dataset", "ptb_char", "--device-data",
            "--steps-per-call", str(k), "--use-pallas", "--scan-unroll", "8",
            "--batch-size", "64", "--seq-len", "64",
            "--num-steps", str(3 * k), "--log-every", "1",
            "--eval-batches", "2", "--seed", str(seed), "--jsonl", jsonl])
    require(rc == 0, f"main.py exited {rc}")
    result = {**train_facts(jsonl), **seen.report()}
    # (no --data-path: the vocabulary is the stand-in's own, unchecked)
    return result, lambda: check_train(result, layers_fused=1)


# ---- serve_http: the server is a process of its own and holds the chip ----


def http_json(url: str, body: dict | None = None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise CheckFailed(f"{url}: HTTP {e.code}: {e.read()[:300]!r}") from e


def serve_http_session(url: str, vocab: int, seed: int) -> dict:
    """A few requests against a booted server; the facts for the check."""
    import random

    rng = random.Random(seed)

    def prompt(n):
        return [rng.randrange(vocab) for _ in range(n)]

    health = http_json(url + "/healthz")
    before = http_json(url + "/stats")
    t0 = time.monotonic()
    replies = [http_json(url + "/v1/generate",
                         {"prompt": prompt(n), "max_new_tokens": 16})
               for n in (5, 12, 40)]
    first = http_json(url + "/v1/generate", {
        "prompt": prompt(9), "max_new_tokens": 8, "keep_session": True})
    second = http_json(url + "/v1/generate", {
        "prompt": [first["tokens"][-1]], "max_new_tokens": 8,
        "session_id": first["session_id"]})
    replies += [first, second]
    seconds = time.monotonic() - t0
    after = http_json(url + "/stats")
    return {
        "healthz": health.get("status", health),
        "requests": len(replies),
        "tokens": [len(r["tokens"]) for r in replies],
        "token_range_ok": all(0 <= t < vocab
                              for r in replies for t in r["tokens"]),
        "continued_session": second["session_id"] == first["session_id"],
        "request_s": seconds / len(replies),
        "compiles_after_warmup": sum(after["compiles"].values()),
        "compiles_during_requests": (sum(after["compiles"].values())
                                     - sum(before["compiles"].values())),
        "decode_kernel": after["decode_kernel"],
        "completed": after["batcher"]["completed"],
        "failed": after["batcher"]["failed"],
        "server_device": after["device"],
    }


def check_serve_http(r: dict) -> None:
    require(r["healthz"] == "ok", f"/healthz said {r['healthz']}")
    require(r["tokens"] == [16, 16, 16, 8, 8] and r["token_range_ok"],
            f"replies carry {r['tokens']} tokens (in range: "
            f"{r['token_range_ok']})")
    require(r["continued_session"], "the continued session changed its id")
    require(r["compiles_during_requests"] == 0,
            f"{r['compiles_during_requests']} compiles after warm-up")
    require(r["completed"] == r["requests"] and r["failed"] == 0,
            f"{r['completed']}/{r['requests']} requests completed")


def phase_serve_http(workdir: str, sizes: Sizes, seed: int):
    sys.path.insert(0, HERE)
    from tools.serve_proc import boot_serve_http

    cmd = [sys.executable, "-m", "lstm_tensorspark_tpu.cli", "serve",
           "--http", "--port", "0",
           "--checkpoint-dir", os.path.join(workdir, "ckpt"),
           "--seed", str(seed), *sizes.serve_flags]
    t0 = time.monotonic()
    # the environment is the caller's: on the chip the server takes the TPU
    proc, lines, url = boot_serve_http(cmd, dict(os.environ),
                                       timeout=PHASE_TIMEOUT_S - 120)
    try:
        if url is None:
            raise CheckFailed("the server never reported its address:\n"
                              + "".join(lines[-40:]))
        result = {"boot_s": round(time.monotonic() - t0, 2),
                  **serve_http_session(url, sizes.vocab, seed)}
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    dev = result.pop("server_device")
    # this child stayed off JAX: the device is what the SERVER reported
    result["device"] = {"platform": dev["platform"], "kind": dev["kind"],
                        "count": dev["process_devices"]}
    result["peak_bytes_in_use"] = [dev["peak_bytes_in_use"]]
    return result, lambda: check_serve_http(result)


# ---- --multichip ----------------------------------------------------------


def phase_dp_train(workdir: str, sizes: Sizes, seed: int):
    """Config 5 data-parallel over four chips (`--backend dp
    --num-partitions 4`) against the one-chip step on the SAME global
    batch, both through main.py in this one process (one process can
    drive all four chips): first-steps loss equal within float tolerance,
    every device holding its shard, the gradient all-reduce compiled in."""
    runs = {}
    with Observed(os.path.join(workdir, "ir_dp")) as seen:
        for name, extra in (
                ("dp4", ["--backend", "dp", "--num-partitions", "4"]),
                ("single", ["--backend", "single"])):
            jsonl = os.path.join(workdir, f"train_{name}.jsonl")
            argv = train_argv(workdir, sizes, seed, jsonl=jsonl, extra=extra)
            # dropout off: its mask is drawn per shard, so only a run
            # without it has one- and four-chip losses that can be compared
            argv[argv.index("--dropout") + 1] = "0.0"
            rc, _ = run_cli(argv)
            require(rc == 0, f"main.py ({name}) exited {rc}")
            runs[name] = {**train_facts(jsonl), "peak": peak_bytes()}
    a, b = runs["dp4"]["losses"], runs["single"]["losses"]
    result = {
        "losses_dp4": a, "losses_single": b,
        "max_rel_diff": max((abs(x - y) / abs(y) for x, y in zip(a, b)),
                            default=None),
        "partitions": runs["dp4"]["partitions"],
        "state_on": runs["dp4"]["state_on"],
        "recurrence": runs["dp4"]["recurrence"],
        "step_s_dp4": runs["dp4"].get("step_s"),
        "step_s_single": runs["single"].get("step_s"),
        # read BEFORE the one-chip run touched device 0 again
        "peak_bytes_after_dp4": runs["dp4"]["peak"],
        "all_reduce_in_lowered_programs": sum(
            t.count("all_reduce") for t in seen.lowered()),
        **seen.report(),
    }

    def check():
        require(result["partitions"] == 4, f"partitions={result['partitions']}")
        require(len(a) == len(b) >= 2, f"losses {a} vs {b}")
        # bf16 matmuls at B/4 rows per chip against B on one: rounding and
        # reduction order differ, nothing else may
        require(result["max_rel_diff"] <= 5e-3,
                f"DP loss {a} != one-chip loss {b} (rel 5e-3)")
        require(len(result["state_on"]) == 4,
                f"train state on devices {result['state_on']}")
        require(result["all_reduce_in_lowered_programs"] > 0,
                "no all_reduce in the lowered DP step")
        require(all(result["peak_bytes_after_dp4"][:4]),
                f"peak bytes per device: {result['peak_bytes_after_dp4']}")

    return result, check


def phase_replicas(workdir: str, sizes: Sizes, seed: int):
    """`cli serve --replicas 4` in one process at config-5 widths: each
    engine's parameters and state cache on its own chip, requests routed
    across all four, every one token-identical to the single-sequence
    reference (which is what one replica is held to)."""
    result, check_tokens = selftest(workdir, "replicas", [
        "--replicas", "4", "--sessions", "12", "--seed", str(seed),
        *sizes.serve_flags], kernel="scan")

    def check():
        check_tokens()
        placed = [r["device"] for r in result["replicas"]]
        require(len(placed) == 4
                and all(d["cache_on"] == d["params_on"]
                        and len(d["cache_on"]) == 1 for d in placed)
                and len({d["cache_on"][0] for d in placed}) == 4,
                f"replica placement: {placed}")
        served = [r["completed"] for r in result["replicas"]]
        require(all(served), f"requests per replica: {served}")

    return result, check


CHILD_PHASES = {
    "train": phase_train, "serve_selftest": phase_serve_selftest,
    "serve_http": phase_serve_http, "small_train": phase_small_train,
    "small_selftest": phase_small_selftest, "dp_train": phase_dp_train,
    "replicas": phase_replicas,
}


def child_main(phase: str, workdir: str, seed: int) -> int:
    """Run one phase; write ``<workdir>/<phase>.json``; rc 0 iff it ran
    AND passed its checks."""
    result = {"phase": phase, "ok": False}
    try:
        if phase == "device":
            result["device"] = device_report()
            require(result["device"]["platform"] == "tpu",
                    f"JAX found no TPU: {result['device']}")
        else:
            out, check = CHILD_PHASES[phase](workdir, FULL, seed)
            result.update(out)
            if "device" not in result:
                result["device"] = device_report()
            check()
        result["ok"] = True
    except CheckFailed as e:
        result["error"] = str(e)
    except Exception as e:  # the boundary: record, report, fail the phase
        import traceback

        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"
    with open(os.path.join(workdir, f"{phase}.json"), "w") as f:
        json.dump(result, f)
    return 0 if result["ok"] else 1


# ---------------------------------------------------------------------------
# parent: never imports JAX
# ---------------------------------------------------------------------------


def write_corpus(directory: str, sizes: Sizes, seed: int) -> None:
    """``wiki.{train,valid,test}.tokens`` (the pattern data/corpus.py
    resolves) with ``sizes.word_types`` distinct words, drawn Zipf-like so
    a few optimizer steps already lower the loss; every type occurs in the
    train split, so the 50,000-word cap of the wikitext103 loader binds."""
    import numpy as np

    os.makedirs(directory, exist_ok=True)
    rng = np.random.default_rng(seed)
    words = np.array([f"w{i:05d}" for i in range(sizes.word_types)])
    p = 1.0 / np.arange(1, sizes.word_types + 1)
    p /= p.sum()
    for split, n in (("train", sizes.train_tokens),
                     ("valid", sizes.train_tokens // 10),
                     ("test", sizes.train_tokens // 10)):
        ids = rng.choice(sizes.word_types, size=n, p=p)
        if split == "train":
            ids[rng.permutation(n)[:sizes.word_types]] = np.arange(
                sizes.word_types)
        with open(os.path.join(directory, f"wiki.{split}.tokens"), "w") as f:
            for i in range(0, n, 32):
                f.write(" ".join(words[ids[i:i + 32]]) + "\n")


def run_child(phase: str, workdir: str, seed: int) -> dict:
    """One phase in a process (group) of its own, bounded and reaped."""
    log = os.path.join(workdir, f"{phase}.log")
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--workdir", workdir, "--seed", str(seed)]
    t0 = time.monotonic()
    with open(log, "w") as out:
        proc = subprocess.Popen(cmd, cwd=HERE, stdout=out,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(PHASE_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            # the phase's whole process group: a server it booted included
            with contextlib.suppress(ProcessLookupError):
                os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
    path = os.path.join(workdir, f"{phase}.json")
    result = {"phase": phase, "ok": False}
    if os.path.exists(path):
        with open(path) as f:
            result = json.load(f)
    if rc is None:
        result.update(ok=False, error=f"timed out after {PHASE_TIMEOUT_S} s")
    elif rc != 0:
        result["ok"] = False
        result.setdefault("error", f"child exited {rc}")
    result["wall_s"] = round(time.monotonic() - t0, 1)
    if not result["ok"]:
        with open(log, errors="replace") as f:
            sys.stderr.write(f"---- {phase}: last lines of {log} ----\n")
            sys.stderr.writelines(f.readlines()[-60:])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="corpus, weights and prompts are made from it")
    ap.add_argument("--multichip", action="store_true",
                    help="the four-chip phases, and no other")
    ap.add_argument("--workdir", default=None,
                    help="keep corpus, checkpoint, logs and lowered "
                         "programs here (default: a temporary directory, "
                         "removed at the end)")
    ap.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return child_main(args.phase, args.workdir, args.seed)

    workdir = args.workdir or tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(workdir, exist_ok=True)
    ok, device = True, None
    try:
        phases = ["device"] + list(MULTICHIP_PHASES if args.multichip
                                   else PHASES)
        for phase in phases:
            if phase in ("train", "dp_train"):
                write_corpus(os.path.join(workdir, "corpus"), FULL, args.seed)
            result = run_child(phase, workdir, args.seed)
            print(json.dumps(result), flush=True)
            ok = ok and result["ok"]
            if device is None:
                device = result.get("device")
            elif result.get("device") not in (None, device):
                ok = False
                print(json.dumps({"error": "phases ran on different "
                                  "devices", "first": device,
                                  "phase": phase}), flush=True)
            if phase == "device" and not ok:
                break  # no TPU: nothing below may run, nothing may pass
            if phase in ("train", "dp_train") and not result["ok"]:
                break  # nothing to serve
        want = 4 if args.multichip else 1
        if ok and device["count"] < want:
            ok = False
            print(json.dumps({"error": f"needs {want} chip(s), JAX reports "
                              f"{device['count']}"}), flush=True)
    finally:
        if not args.workdir:
            shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
