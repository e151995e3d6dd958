"""The host's own cost of one dispatch of K steps: ``train:feed`` +
``train:dispatch`` + ``train:log`` from one pull of the feed to the next,
median over the dispatches of the traced window. It bounds the rate once it
nears K x `train_step_ms`."""
import statistics

import program_spans

PARTS = ("train:feed", "train:dispatch", "train:log")


def read(result, cell):
    spans = program_spans.thread_in_window(
        result, cell, program_spans.TRAINER_ANCHOR)
    per_dispatch = []
    for s in spans:
        if s.name == "train:feed":
            per_dispatch.append([0.0, False])
        if s.name in PARTS and per_dispatch:
            per_dispatch[-1][0] += s.seconds
            per_dispatch[-1][1] |= s.name == "train:dispatch"
    costs = [seconds for seconds, dispatched in per_dispatch if dispatched]
    return 1e3 * statistics.median(costs) if costs else None
