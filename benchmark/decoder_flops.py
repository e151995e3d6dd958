"""Operations and bytes of the decoder family from shapes: what the per-layer
readers of a ``decoder_serve`` cell divide by the published peaks. ``doc`` is
the configuration file (the published keys; ``n_routed_experts`` the experts
held here, ``vocab_size`` the slice). Counts are of what the ALGORITHM needs
(576 values a cached row, not the 640 lanes it is padded to; each touched
expert's weights once), so a share of a roofline reads the same work whatever
implements it."""

from __future__ import annotations


def shapes(doc: dict) -> dict:
    d, h = doc["hidden_size"], doc["num_attention_heads"]
    qd = doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]
    kv, rope = doc["kv_lora_rank"], doc["qk_rope_head_dim"]
    layers, dense = doc["num_hidden_layers"], doc["first_k_dense_replace"]
    return {
        "d": d, "heads": h, "row": kv + rope, "kv": kv,
        "layers": layers, "moe_layers": layers - dense, "dense_layers": dense,
        # parameters a token multiplies by, per layer
        "attention": (d * doc["q_lora_rank"] + doc["q_lora_rank"] * h * qd
                      + d * (kv + rope)
                      + kv * h * (doc["qk_nope_head_dim"] + doc["v_head_dim"])
                      + h * doc["v_head_dim"] * d),
        "dense_mlp": 3 * d * doc["intermediate_size"],
        "shared": 3 * d * doc["moe_intermediate_size"] * doc["n_shared_experts"],
        "router": d * doc.get("published", doc)["n_routed_experts"],
        "expert": 3 * d * doc["moe_intermediate_size"],
        "head": d * doc["vocab_size"],
    }


def always_read_params(doc: dict) -> float:
    """Parameters every decode step reads whatever the batch: attention of
    every layer, the dense MLPs, the shared experts and routers, the head."""
    s = shapes(doc)
    return (s["layers"] * s["attention"] + s["dense_layers"] * s["dense_mlp"]
            + s["moe_layers"] * (s["shared"] + s["router"]) + s["head"])


def token_linear_flops(doc: dict, *, head: bool) -> float:
    """Matmul FLOPs of one token outside attention's scores and the routed
    experts (those are counted from the counters)."""
    s = shapes(doc)
    p = (s["layers"] * s["attention"] + s["dense_layers"] * s["dense_mlp"]
         + s["moe_layers"] * (s["shared"] + s["router"]))
    return 2.0 * (p + (s["head"] if head else 0))


def pair_flops(doc: dict) -> float:
    """One (token, expert) pair through one routed expert."""
    return 2.0 * shapes(doc)["expert"]


def attention_flops(doc: dict, query_key_pairs: float) -> float:
    """Scores and weighted sum of the absorbed form, all heads and layers:
    per (query, key) pair and head, ``row`` multiply-adds for the score and
    ``kv`` for the sum."""
    s = shapes(doc)
    return 2.0 * query_key_pairs * s["heads"] * (s["row"] + s["kv"]) * s["layers"]


def latent_bytes(doc: dict, tokens: float, itemsize: int = 2) -> float:
    """Cached rows of ``tokens`` tokens, every layer."""
    s = shapes(doc)
    return float(tokens) * s["row"] * itemsize * s["layers"]


def decode_step(doc: dict, *, rows: float, context_tokens: float,
                experts_touched: float, pairs_here: float,
                itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of ONE decode step of ``rows`` live rows whose
    contexts add up to ``context_tokens``, which touched ``experts_touched``
    experts (summed over the expert layers) with ``pairs_here`` pairs."""
    s = shapes(doc)
    flops = (rows * token_linear_flops(doc, head=True)
             + pairs_here * pair_flops(doc)
             + attention_flops(doc, context_tokens))
    bytes_ = (itemsize * (always_read_params(doc)
                          + experts_touched * s["expert"])
              + latent_bytes(doc, context_tokens, itemsize))
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    t_f = flops / (peak["bf16_tflops"] * 1e12)
    t_b = bytes_ / (peak["hbm_gbytes_per_s"] * 1e9)
    return (t_f, "FLOPs") if t_f >= t_b else (t_b, "HBM bytes")
