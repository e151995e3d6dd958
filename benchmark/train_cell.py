"""A training cell: the configuration's job through `cli.main`, the entry
point `python main.py` calls, in this process.

Two calls. The first is short: it compiles (or fetches from the compile
cache) and gives the time of a step, from which the second call's step
budget follows — `main.py` has no time limit of its own (PERF.md §7). The
second is the measured one: its window opens at the first synced log record
(so the first dispatches, which trace and fetch the program again, are
outside) and closes at the last. Each record is stamped here, on the
benchmark's clock, at the instant the program prints it — right after it has
read the loss back from the device, i.e. after the work is finished.
"""

from __future__ import annotations

import contextlib
import io
import json
import math
import os
import re
import statistics
import sys
import time

import observe
import trace_reduce
from corpus import write_corpus

RECORD = re.compile(r"\bsteps_per_sec=")
SYNC_MARK = "bench:log_record"


class StampedOutput(io.TextIOBase):
    """Stands in for stdout while the program runs: passes everything on to
    stderr and notes when each step record was printed."""

    def __init__(self, annotate: bool):
        self.stamps: list[float] = []
        self.annotate = annotate

    def writable(self):
        return True

    def write(self, s):
        if RECORD.search(s):
            self.stamps.append(time.perf_counter())
            if self.annotate:
                import jax.profiler

                with jax.profiler.TraceAnnotation(SYNC_MARK):
                    pass
        sys.stderr.write(s)
        return len(s)

    def flush(self):
        sys.stderr.flush()


def run_cli(argv: list[str], *, annotate: bool = False) -> list[float]:
    """`cli.main(argv)`; returns the stamps of its step records."""
    from lstm_tensorspark_tpu.cli import main

    out = StampedOutput(annotate)
    with contextlib.redirect_stdout(out):
        rc = main(argv)
    if rc != 0:
        raise SystemExit(f"main.py exited {rc}")
    return out.stamps


def read_jsonl(path: str):
    with open(path) as f:
        records = [json.loads(line) for line in f]
    start = next(r for r in records if r.get("note") == "start")
    steps = [r for r in records if "steps_per_sec" in r]
    return start, steps


def model_config(model: dict, *, use_pallas: bool):
    from lstm_tensorspark_tpu.models import LMConfig

    return LMConfig(vocab_size=model["vocab_size"],
                    hidden_size=model["hidden_size"],
                    num_layers=model["num_layers"],
                    compute_dtype=model["compute_dtype"],
                    logits_dtype=model["logits_dtype"],
                    use_pallas=use_pallas)


#: The check's weights are `init_lm`'s with the embedding times 50 and the
#: head times 8. At `init_lm`'s own scale (embedding N(0, 0.02^2)) every gate
#: sits in its linear range and the recurrent term is one part in 1e4 of the
#: loss: zeroing U, swapping two gates or dropping two layers all moved a
#: loss-only check by less than its tolerance (REVIEW, PR 25). Scaled, the
#: inputs have unit variance, pre-activations are of order one, h*U weighs as
#: much as x*W from the second layer up and the logits reach a few units, so
#: each of those faults moves hidden states and gradients by their own size.
CHECK_SCALE = {"embedding": 50.0, "head": 8.0}

#: How far the program (bfloat16 matmul inputs and logits, the cell's
#: kernels) may lie from the float32 reference, each as a share of the
#: reference's own size. `rel_l2` is |got - want|_2 / |want|_2 over a whole
#: array, `rel_max` is max|got - want| / max|want|. The reasons, with what
#: the chip read: a bf16 rounding is 2^-9 of a value, a sum over 1,024 of
#: them averages out to about that again, and four layers of 128 recurrent
#: steps carry it along; gradients pass through every rounding twice. The
#: v5e's kernels read 0.0068 (hidden, L2), 0.019 (hidden, largest), 9e-6
#: (loss) and 0.011 (worst gradient, layer 3's U_f) at 64 x 128 (PERF.md
#: section 2), so each tolerance is four to seven times what a correct
#: program shows; a fault of the kinds named above reads 0.96 to 1.4 on
#: hidden states and 1.0 on gradients (tests/test_train_check.py, at the
#: published widths).
TOLERANCE = {"hidden_rel_l2": 2.0 ** -5, "hidden_rel_max": 2.0 ** -3,
             "loss_of_max_logit": 2.0 ** -6, "grad_rel_l2": 2.0 ** -4}


def check_params(seed: int, cfg):
    import jax

    from lstm_tensorspark_tpu.models import init_lm

    kparams, _ = jax.random.split(jax.random.PRNGKey(seed))
    params = init_lm(kparams, cfg)
    head = dict(params["head"], kernel=params["head"]["kernel"] * CHECK_SCALE["head"])
    return dict(params, head=head,
                embedding=params["embedding"] * CHECK_SCALE["embedding"])


def program_outputs(params, cfg, inputs, targets) -> dict:
    """What the program computes at one chip's shape: the top layer's
    hidden states, the loss and its gradient in every parameter, through
    `lm_backbone` / `lm_loss` with the cell's kernels and dtypes (dropout
    off, so that it is a function of the weights alone)."""
    import jax

    from lstm_tensorspark_tpu.models import lm_loss
    from lstm_tensorspark_tpu.models.lstm_lm import lm_backbone

    batch = {"inputs": inputs, "targets": targets}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(p, batch, cfg)[0]))(params)
    hidden = jax.jit(lambda p: lm_backbone(p, inputs, cfg)[1])(params)
    return {"loss": loss, "hidden": hidden, "grads": grads}


def reference_outputs(params, inputs, targets, *, slices: int = 4) -> dict:
    """The same three from `reference/lstm_lm.py` (float32, precision
    "highest", `lax.scan`, `jax.grad`), in slices of rows so that the
    [rows, T, V] float32 logits and their gradient stay small."""
    import jax
    import jax.numpy as jnp

    from reference import lstm_lm as reference

    value_and_grad = jax.jit(jax.value_and_grad(reference.loss, has_aux=True))
    hidden_of = jax.jit(lambda p, x: reference.hidden_states(p, x)[0])
    batch = inputs.shape[0]
    rows = -(-batch // slices)
    loss, top, grads, hidden = 0.0, 0.0, None, []
    for i in range(0, batch, rows):
        x, y = inputs[i:i + rows], targets[i:i + rows]
        share = x.shape[0] / batch
        (part, peak), g = value_and_grad(params, x, y)
        loss += share * float(part)
        top = max(top, float(peak))
        g = jax.tree.map(lambda a: share * a, g)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        hidden.append(hidden_of(params, x))
    return {"loss": loss, "max_logit": top, "grads": grads,
            "hidden": jnp.concatenate(hidden)}


def compare(got: dict, want: dict, tolerance: dict = TOLERANCE) -> dict:
    """`got` (the program's outputs) held to `want` (the reference's)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def distances(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return (jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()),
                jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    hidden_l2, hidden_max = (float(x) for x in distances(got["hidden"], want["hidden"]))
    leaves = jax.tree_util.tree_flatten_with_path(want["grads"])[0]
    grad_l2 = {jax.tree_util.keystr(path): float(distances(g, w)[0])
               for (path, w), g in zip(leaves, jax.tree.leaves(got["grads"]))}
    worst = max(grad_l2, key=grad_l2.get)
    loss_gap = abs(float(got["loss"]) - want["loss"])
    report = {"hidden_rel_l2": hidden_l2, "hidden_rel_max": hidden_max,
              "loss": float(got["loss"]), "reference_loss": want["loss"],
              "loss_of_max_logit": loss_gap / want["max_logit"],
              "grad_rel_l2": grad_l2[worst], "grad_worst_leaf": worst,
              "tolerance": tolerance}
    report["ok"] = all(math.isfinite(report[k]) and report[k] <= tolerance[k]
                       for k in tolerance)
    return report


def reference_check(cell, batch: int, seq_len: int, vocab: int) -> dict:
    """The program against the plain reference on the check's weights
    (`CHECK_SCALE`) at the published widths and a seeded batch of one
    chip's shape: forward kernels (hidden states), head and loss (loss),
    backward kernels and everything around them (gradients)."""
    import jax.numpy as jnp
    import numpy as np

    model = cell.config["model"]
    cfg = model_config(model, use_pallas="--use-pallas" in cell.config["train"]["flags"])
    params = check_params(cell.seed, cfg)
    rng = np.random.default_rng([cell.seed, 0xC4EC])
    tokens = rng.integers(2, vocab, size=(batch, seq_len + 1)).astype(np.int32)
    inputs, targets = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    return compare(program_outputs(params, cfg, inputs, targets),
                   reference_outputs(params, inputs, targets))


def run(cell) -> dict:
    """Returns the cell's result (see `run.py`)."""
    config, traffic = cell.config, cell.traffic
    train, model = config["train"], config["model"]
    corpus_dir = os.path.join(cell.workdir, "corpus")
    write_corpus(corpus_dir, seed=cell.seed, **train["corpus"])
    flags = traffic["flags"]
    batch = int(flags[flags.index("--batch-size") + 1])
    seq_len = int(flags[flags.index("--seq-len") + 1])
    k = int(train["flags"][train["flags"].index("--steps-per-call") + 1])
    log_every = int(traffic["log_every_dispatches"])
    base = ["--dataset", train["dataset"],
            "--hidden-units", str(model["hidden_size"]),
            "--num-layers", str(model["num_layers"]),
            "--compute-dtype", model["compute_dtype"],
            "--logits-dtype", model["logits_dtype"],
            *train["flags"], *flags, "--eval-batches", "1",
            "--seed", str(cell.seed), "--data-path", corpus_dir]

    marks = {"start": time.perf_counter() - cell.t0}
    check = reference_check(cell, batch // cell.chips, seq_len,
                            model["vocab_size"])
    marks["reference_check"] = time.perf_counter() - cell.t0
    marks["peak_after_reference"] = observe.peak_bytes()

    # call 1: compile, and the time of a step
    jsonl1 = os.path.join(cell.workdir, "calibrate.jsonl")
    stamps = run_cli(base + ["--num-steps", str(4 * k), "--log-every", "1",
                             "--jsonl", jsonl1])
    step_s = statistics.median(
        b - a for a, b in zip(stamps[1:], stamps[2:])) / k
    marks["calibration_call"] = time.perf_counter() - cell.t0
    marks["peak_after_calibration"] = observe.peak_bytes()

    # call 2: the measured one
    seconds = min(cell.seconds, traffic["trace_seconds"]) if cell.trace \
        else cell.seconds
    intervals = max(math.ceil(seconds / (step_s * k * log_every)), 2)
    jsonl2 = os.path.join(cell.workdir, "measured.jsonl")
    argv = base + ["--num-steps", str((intervals + 1) * log_every * k),
                   "--log-every", str(log_every), "--jsonl", jsonl2]
    profile_dir = None
    if cell.trace:
        profile_dir = os.path.join(cell.workdir, "profile")
        argv += ["--profile-dir", profile_dir]
    stamps = run_cli(argv, annotate=cell.trace)
    start, records = read_jsonl(jsonl2)
    if len(stamps) != len(records) or len(records) < 3:
        raise SystemExit(f"{len(stamps)} records stamped, {len(records)} in "
                         "the JSONL: cannot place the window")
    opened, closed = stamps[0], stamps[-1]
    steps = records[-1]["step"] - records[0]["step"]
    per_step = [(b - a) / (rb["step"] - ra["step"])
                for a, b, ra, rb in zip(stamps, stamps[1:], records, records[1:])]
    late_compiles = cell.compiles.between(opened, closed)
    losses = [r["loss"] for r in records]
    recurrence = start["recurrence"]
    wants_kernels = "--use-pallas" in train["flags"]
    correct = {
        "reference": check["ok"],
        "losses_finite": all(math.isfinite(x) for x in losses),
        "loss_fell": losses[-1] < losses[0],
        "vocab": start["vocab"] == model["vocab_size"],
        "recurrence": (not wants_kernels or cell.rehearsal
                       or (recurrence.startswith("pallas fwd=")
                           and "recompute" not in recurrence)),
        "partitions": start["partitions"] == cell.chips,
        "no_compile_in_window": not late_compiles,
    }
    tokens_per_s = steps * batch * seq_len / (closed - opened)
    result = {
        "correct": all(correct.values()), "checks": correct,
        "attempted": steps,
        "failed": int(sum(r.get("anomalous", 0) for r in records[1:])),
        "setup_s": opened - cell.t0,
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "samples": {"train_tokens_per_s":
                    f"{steps} optimizer steps over {closed - opened:.3f} s, "
                    f"{len(per_step)} log intervals",
                    "reference": check, "recurrence": recurrence,
                    "calibrated_step_s": step_s,
                    "setup_marks_s": marks},
        # what the per-layer readers may read
        "window_s": closed - opened, "steps": steps, "per_step_s": per_step,
        "batch": batch, "seq_len": seq_len, "steps_per_call": k,
        "late_compiles": late_compiles, "recurrence": recurrence,
        "trace": None, "trace_window": None, "sync_mark": SYNC_MARK,
    }
    if profile_dir:
        path = trace_reduce.find_xplane(profile_dir)
        if path:
            result["trace"] = trace_reduce.load(path)
    return result
