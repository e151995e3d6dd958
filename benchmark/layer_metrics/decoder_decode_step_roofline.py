"""One decode step's share of its roofline: the larger of its bytes over the
HBM peak and its FLOPs over the bf16 peak, over its device time. The bytes
are what the step MUST read: attention, shared-expert, dense, router and head
weights once, the experts TOUCHED (the device's counter, not the experts
held), the latents of the rows' contexts."""
import decoder_flops
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    busy = decoder_readers.busy_in_modules(result, cell, decoder_readers.DECODE_PROGRAM)
    if not d or not busy or not d["decode_steps"]:
        return None
    steps = d["decode_steps"]
    flops_, bytes_ = decoder_flops.decode_step(
        cell.config, rows=d["decode_row_steps"] / steps,
        context_tokens=d["decode_context_tokens"] / steps,
        experts_touched=d["decode_experts_touched"] / steps,
        pairs_here=d["decode_moe_pairs_here"] / steps,
        itemsize=result["param_bytes"])
    least, bound = decoder_flops.least_seconds(flops_, bytes_, decoder_readers.peaks())
    result["samples"]["decoder_decode_step_roofline"] = (
        f"{steps} steps, {d['decode_row_steps'] / steps:.2f} rows, "
        f"{d['decode_experts_touched'] / steps:.1f} experts touched a step, "
        f"{bytes_ / 1e9:.3f} GB and {flops_ / 1e9:.1f} GFLOP a step: bound by "
        f"{bound}, least {1e3 * least:.3f} ms, measured {1e3 * busy / steps:.3f} ms")
    return 100.0 * least / (busy / steps)
