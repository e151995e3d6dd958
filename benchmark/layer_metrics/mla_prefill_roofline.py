"""The prefill attention kernel's share of its roofline (``mla_prefill`` in
the device trace): the larger of the (query, key) pairs' FLOPs over the bf16
peak and the prefilled rows' contexts' latents (read once a row) over the HBM
peak, over the kernel's time."""
import decoder_flops
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    t = decoder_readers.kernel_seconds(result, cell, "mla_prefill")
    if not d or not t:
        return None
    least, bound = decoder_flops.least_seconds(
        decoder_flops.attention_flops(cell.config, d["prefill_attended"]),
        decoder_flops.latent_bytes(cell.config, d["prefill_context_tokens"],
                                   result["param_bytes"]),
        decoder_readers.peaks())
    result["samples"]["mla_prefill_roofline"] = (
        f"bound by {bound}: least {1e3 * least:.3f} ms, measured {1e3 * t:.3f} ms, "
        f"{d['prefill_tokens']} tokens prefilled")
    return 100.0 * least / t
