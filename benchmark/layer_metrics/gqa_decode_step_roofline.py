"""One decode step's share of its roofline, for the K/V decoder: the larger
of its bytes over the HBM peak and its FLOPs over the bf16 peak, over its
device time. The bytes are what the step MUST read: attention, router and
head weights once, the experts TOUCHED (the device's counter), the keys and
values inside each row's mask, by kind of layer (a window layer's row reads
at most its window)."""
import decoder_flops
import decoder_readers
import gqa_decoder_flops


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    busy = decoder_readers.busy_in_modules(result, cell, decoder_readers.DECODE_PROGRAM)
    if not d or not busy or not d["decode_steps"] or "decode_window_keys_read" not in d:
        return None
    steps = d["decode_steps"]
    flops_, bytes_ = gqa_decoder_flops.decode_step(
        cell.config, rows=d["decode_row_steps"] / steps,
        full_keys=d["decode_full_keys_read"] / steps,
        window_keys=d["decode_window_keys_read"] / steps,
        experts_touched=d["decode_experts_touched"] / steps,
        pairs=d["decode_moe_pairs_here"] / steps,
        itemsize=result["param_bytes"])
    least, bound = decoder_flops.least_seconds(flops_, bytes_, decoder_readers.peaks())
    kv = gqa_decoder_flops.kv_bytes(
        cell.config, d["decode_full_keys_read"] / steps,
        d["decode_window_keys_read"] / steps, result["param_bytes"])
    experts = (d["decode_experts_touched"] / steps * result["param_bytes"]
               * gqa_decoder_flops.shapes(cell.config)["expert"])
    result["samples"]["gqa_decode_step_roofline"] = (
        f"{steps} steps, {d['decode_row_steps'] / steps:.2f} rows, "
        f"{d['decode_experts_touched'] / steps:.1f} experts touched a step, "
        f"{bytes_ / 1e9:.3f} GB ({experts / 1e9:.3f} experts, {kv / 1e9:.3f} keys "
        f"and values) and {flops_ / 1e9:.1f} GFLOP a step: bound by {bound}, "
        f"least {1e3 * least:.3f} ms, measured {1e3 * busy / steps:.3f} ms")
    return 100.0 * least / (busy / steps)
