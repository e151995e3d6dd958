"""The decode attention kernel's share of its roofline: the larger of the
rows' contexts' latents over the HBM peak and the absorbed form's FLOPs over
the bf16 peak, over the kernel's time in the trace."""
import decoder_flops
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    t = decoder_readers.kernel_seconds(result, cell, "mla_decode")
    if not d or not t:
        return None
    least, bound = decoder_flops.least_seconds(
        decoder_flops.attention_flops(cell.config, d["decode_context_tokens"]),
        decoder_flops.latent_bytes(cell.config, d["decode_context_tokens"],
                                   result["param_bytes"]),
        decoder_readers.peaks())
    result["samples"]["mla_decode_roofline"] = (
        f"bound by {bound}: least {1e3 * least:.3f} ms, measured {1e3 * t:.3f} ms "
        f"over {d['decode_context_tokens']} cached tokens read")
    return 100.0 * least / t
