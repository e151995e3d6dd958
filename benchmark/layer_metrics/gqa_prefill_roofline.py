"""The grouped-query prefill attention kernel's share of its roofline
(``gqa_prefill`` in the device trace): the larger of the (query, key) pairs'
FLOPs over the bf16 peak and the keys and values the prefilled rows must
read once over the HBM peak (a full layer: the row's whole context; a window
layer: counted as the new tokens alone, an undercount), over the kernel's
time."""
import decoder_flops
import decoder_readers
import gqa_decoder_flops


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    t = decoder_readers.kernel_seconds(result, cell, "gqa_prefill")
    if not d or not t or "prefill_window_pairs" not in d:
        return None
    least, bound = decoder_flops.least_seconds(
        gqa_decoder_flops.attention_flops(
            cell.config, d["prefill_full_pairs"], d["prefill_window_pairs"]),
        gqa_decoder_flops.kv_bytes(
            cell.config, d["prefill_context_tokens"], d["prefill_tokens"],
            result["param_bytes"]),
        decoder_readers.peaks())
    result["samples"]["gqa_prefill_roofline"] = (
        f"bound by {bound}: least {1e3 * least:.3f} ms, measured {1e3 * t:.3f} ms, "
        f"{d['prefill_tokens']} tokens prefilled")
    return 100.0 * least / t
