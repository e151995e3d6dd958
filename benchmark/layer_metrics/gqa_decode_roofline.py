"""The grouped-query decode attention kernel's share of its roofline: the
larger of the keys and values inside the rows' masks (a window layer's row:
at most its window) over the HBM peak and the pairs' FLOPs over the bf16
peak, over the kernel's time in the trace."""
import decoder_flops
import decoder_readers
import gqa_decoder_flops


def read(result, cell):
    d = decoder_readers.delta(result, "trace_counters", "decoder")
    t = decoder_readers.kernel_seconds(result, cell, "gqa_decode")
    if not d or not t or "decode_window_keys_read" not in d:
        return None
    full, window = d["decode_full_keys_read"], d["decode_window_keys_read"]
    least, bound = decoder_flops.least_seconds(
        gqa_decoder_flops.attention_flops(cell.config, full, window),
        gqa_decoder_flops.kv_bytes(cell.config, full, window, result["param_bytes"]),
        decoder_readers.peaks())
    result["samples"]["gqa_decode_roofline"] = (
        f"bound by {bound}: least {1e3 * least:.3f} ms, measured {1e3 * t:.3f} ms "
        f"over {full} keys read in a full layer, {window} in a window layer")
    return 100.0 * least / t
