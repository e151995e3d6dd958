"""Share of the chip's bf16 peak the whole window used, for the K/V decoder:
the FLOPs of the tokens prefilled and decoded in the window (linear layers
per token, the experts from the (token, expert) pairs, attention from the
(query, key) pairs inside each kind of layer's mask) over window x peak.
Small by nature: decoding is bound by bytes."""
import decoder_readers
import gqa_decoder_flops


def read(result, cell):
    d = decoder_readers.delta(result, "counters", "decoder")
    if d is None or "prefill_window_pairs" not in d:
        return None
    doc = cell.config
    total = (d["prefill_tokens"] * gqa_decoder_flops.token_linear_flops(doc, head=False)
             + d["decode_row_steps"] * gqa_decoder_flops.token_linear_flops(doc, head=True)
             + d["moe_pairs_here"] * gqa_decoder_flops.pair_flops(doc)
             + gqa_decoder_flops.attention_flops(
                 doc, d["prefill_full_pairs"] + d["decode_full_keys_read"],
                 d["prefill_window_pairs"] + d["decode_window_keys_read"]))
    peak = decoder_readers.peaks()["bf16_tflops"] * 1e12
    result["samples"]["gqa_decoder_serve_mfu"] = (
        f"{total / 1e12:.3f} TFLOP in {result['window_s']} s: "
        f"{d['prefill_tokens']} tokens prefilled, {d['decode_row_steps']} decoded")
    return 100.0 * total / (result["window_s"] * peak)
