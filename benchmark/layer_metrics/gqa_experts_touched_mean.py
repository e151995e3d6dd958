"""Experts (of the 64 a layer holds) that one decode step touched in one
layer, mean over the window's steps and layers: what the step's expert bytes
are."""
import decoder_readers
import gqa_decoder_flops


def read(result, cell):
    d = decoder_readers.delta(result, "counters", "decoder")
    if not d or not d["decode_steps"]:
        return None
    layers = gqa_decoder_flops.shapes(cell.config)["layers"]
    return d["decode_experts_touched"] / (d["decode_steps"] * layers)
