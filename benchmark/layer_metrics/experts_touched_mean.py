"""Experts (of those held) that one decode step touched in one expert layer,
mean over the window's steps and layers: what the step's expert bytes are."""
import decoder_flops
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "counters", "decoder")
    if not d or not d["decode_steps"]:
        return None
    layers = decoder_flops.shapes(cell.config)["moe_layers"]
    return d["decode_experts_touched"] / (d["decode_steps"] * layers)
