"""The grouped expert products' share of their roofline in the K/V decoder's
decode steps: the larger of the TOUCHED experts' weights over the HBM peak
and the pairs' FLOPs over the bf16 peak, at this configuration's widths,
over the kernels' time inside the decode programs."""
import decoder_flops
import decoder_readers
import gqa_decoder_flops


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    t = decoder_readers.kernel_seconds(result, cell, "expert_gmm",
                                       decoder_readers.DECODE_PROGRAM)
    if not d or not t:
        return None
    s = gqa_decoder_flops.shapes(cell.config)
    least, bound = decoder_flops.least_seconds(
        d["decode_moe_pairs_here"] * gqa_decoder_flops.pair_flops(cell.config),
        d["decode_experts_touched"] * s["expert"] * result["param_bytes"],
        decoder_readers.peaks())
    result["samples"]["gqa_expert_gmm_roofline"] = (
        f"bound by {bound}: least {1e3 * least:.3f} ms, measured {1e3 * t:.3f} ms, "
        f"{d['decode_experts_touched']} expert reads, {d['decode_moe_pairs_here']} pairs")
    return 100.0 * least / t
