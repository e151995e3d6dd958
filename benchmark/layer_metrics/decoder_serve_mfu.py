"""Share of the chip's bf16 peak the whole window used: the FLOPs of the
tokens prefilled and decoded in the window as computed on this chip (linear
layers per token, the routed experts from the pairs that landed here,
attention from the (query, key) pairs) over window x peak. Small by nature:
decoding is bound by bytes."""
import decoder_flops
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "counters", "decoder")
    if d is None:
        return None
    doc = cell.config
    total = (d["prefill_tokens"] * decoder_flops.token_linear_flops(doc, head=False)
             + d["decode_row_steps"] * decoder_flops.token_linear_flops(doc, head=True)
             + d["moe_pairs_here"] * decoder_flops.pair_flops(doc)
             + decoder_flops.attention_flops(
                 doc, d["prefill_attended"] + d["decode_context_tokens"]))
    peak = decoder_readers.peaks()["bf16_tflops"] * 1e12
    result["samples"]["decoder_serve_mfu"] = (
        f"{total / 1e12:.3f} TFLOP in {result['window_s']} s: "
        f"{d['prefill_tokens']} tokens prefilled, {d['decode_row_steps']} decoded")
    return 100.0 * total / (result["window_s"] * peak)
