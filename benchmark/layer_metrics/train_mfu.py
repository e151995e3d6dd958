"""End-to-end model-FLOPs utilisation: tokens/s of the whole job times the
FLOPs training one token requires, over chips times the bf16 peak. Not a
roofline share: idle time and recomputation are inside it."""
import flops


def read(result, cell):
    import jax

    m = cell.config["model"]
    per_token = flops.lm_train_flops_per_token(
        m["vocab_size"], m["hidden_size"], m["num_layers"], m.get("embed_size"))
    peak = flops.peaks(jax.devices()[0].device_kind)["bf16_tflops"] * 1e12
    rate = result["end_to_end"]["train_tokens_per_s"]
    return 100.0 * rate * per_token / (cell.chips * peak)
