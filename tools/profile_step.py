#!/usr/bin/env python
"""Per-kernel device-time breakdown of any bench.py config's train step
on the real chip: trace a few K-step dispatches of EXACTLY the program
`bench.py`'s measure_config times (make_multi_train_step over a staged
synthetic batch at real model dims), parse the xplane with
jax.profiler.ProfileData, and aggregate kernel durations per optimizer
step.

Usage: python tools/profile_step.py [config] [K]
  config: ptb_char (default) | imdb_bilstm | wikitext2 | uci_seq2seq
          | wikitext103
  K:      steps per traced dispatch (default 32)

This is the diagnostic that found the vocabulary-indexing bottleneck
(ops/embedding.py): at ptb_char it showed 43 us/step in the target-logit
gather and 28 us/step in the embedding-grad scatter vs 29 us/step for the
fused Pallas recurrence pair — 48% of the step in indexing; after the fix
the same trace reads ~78 us/step with both kernels gone. Rerun it whenever
a config's measured step time drifts from its roofline bound
(bench.py's `roofline` record) to see where the slack actually is.
"""

import collections
import glob
import os
import shutil
import sys

import jax

PROF_DIR = "/tmp/prof_step"


def build_step(name: str, k: int):
    """Mirror bench.measure_config's program construction."""
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import bench

    from lstm_tensorspark_tpu.train import make_multi_train_step, make_optimizer
    from lstm_tensorspark_tpu.train.loop import init_train_state

    c = bench.CONFIGS[name]
    kind = c["kind"]
    if kind == "lm":
        from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
        cfg = LMConfig(vocab_size=c["V"], hidden_size=c["H"],
                       num_layers=c["L"], compute_dtype="bfloat16",
                       logits_dtype=c.get("logits_dtype", "float32"),
                       use_pallas=True)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        loss_fn = lambda p, b, r: lm_loss(p, b, cfg)  # noqa: E731
    elif kind == "classifier":
        from lstm_tensorspark_tpu.models import (
            ClassifierConfig, classifier_loss, init_classifier,
        )
        cfg = ClassifierConfig(vocab_size=c["V"], hidden_size=c["H"],
                               num_layers=c["L"], compute_dtype="bfloat16",
                               use_pallas=True)
        params = init_classifier(jax.random.PRNGKey(0), cfg)
        loss_fn = lambda p, b, r: classifier_loss(p, b, cfg)  # noqa: E731
    else:
        from lstm_tensorspark_tpu.models import (
            Seq2SeqConfig, init_seq2seq, seq2seq_loss,
        )
        cfg = Seq2SeqConfig(num_features=c["F"], hidden_size=c["H"],
                            num_layers=c["L"], horizon=c["horizon"],
                            compute_dtype="bfloat16", use_pallas=True)
        params = init_seq2seq(jax.random.PRNGKey(0), cfg)
        loss_fn = lambda p, b, r: seq2seq_loss(p, b, cfg)  # noqa: E731

    opt = make_optimizer("sgd", 0.1)
    state = init_train_state(params, opt, jax.random.PRNGKey(1))
    step = make_multi_train_step(loss_fn, opt)
    batch = bench._rand_batch(kind, c, jax.random.PRNGKey(2))
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (k, *a.shape)), batch
    )
    stacked = jax.device_put(stacked)
    return step, state, stacked


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "ptb_char"
    k = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    step, state, stacked = build_step(name, k)
    for _ in range(3):
        state, m = step(state, stacked)
    float(m["loss"])

    shutil.rmtree(PROF_DIR, ignore_errors=True)
    calls = max(1, 256 // k)
    with jax.profiler.trace(PROF_DIR):
        for _ in range(calls):
            state, m = step(state, stacked)
        float(m["loss"])

    paths = glob.glob(os.path.join(PROF_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        print("no xplane written", file=sys.stderr)
        return 1
    pd = jax.profiler.ProfileData.from_file(paths[0])
    for pl in pd.planes:
        if "TPU" not in pl.name and "Device" not in pl.name:
            continue
        agg = collections.defaultdict(lambda: [0.0, 0])
        t_min, t_max = float("inf"), 0.0
        for line in pl.lines:
            for ev in line.events:
                dur = (ev.duration_ns or 0) / 1e3  # us
                agg[ev.name][0] += dur
                agg[ev.name][1] += 1
                if ev.start_ns:
                    t_min = min(t_min, ev.start_ns)
                    t_max = max(t_max, ev.start_ns + (ev.duration_ns or 0))
        steps_total = calls * k
        span_us = (t_max - t_min) / 1e3 if t_max > t_min else 0.0
        print(f"\n=== {name} plane {pl.name}: {steps_total} optimizer "
              f"steps, trace span {span_us:.0f} us "
              f"({span_us / steps_total:.2f} us/step) ===")
        rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
        total = sum(v[0] for _, v in rows)
        print(f"{'us/step':>9} {'count/step':>11} {'pct':>5}  kernel")
        for kname, (dur, cnt) in rows[:40]:
            print(f"{dur / steps_total:9.3f} {cnt / steps_total:11.2f} "
                  f"{100 * dur / total:5.1f}  {kname[:100]}")
        print(f"{total / steps_total:9.3f} {'':>11} 100.0  TOTAL device time")
    return 0


if __name__ == "__main__":
    sys.exit(main())
