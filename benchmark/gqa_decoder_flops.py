"""Operations and bytes of the K/V decoder (``model_type: "mellum"``:
grouped-query attention, window and full layers, every expert held) from
shapes: what the per-layer readers of a ``gqa_decoder_serve`` cell divide by
the published peaks. ``doc`` is the configuration file (the published keys at
its top level). Counts are of what the ALGORITHM needs: a key and a value of
``head_dim`` per key/value head and token; a window layer's query reads at
most ``sliding_window`` keys (the engine counts the (query, key) pairs inside
each kind's mask); each touched expert's weights once. So a share of a
roofline reads the same work whatever implements it."""

from __future__ import annotations


def shapes(doc: dict) -> dict:
    d, h, g, hd = (doc["hidden_size"], doc["num_attention_heads"],
                   doc["num_key_value_heads"], doc["head_dim"])
    kinds = doc["layer_types"]
    return {
        "d": d, "heads": h, "kv_heads": g, "head_dim": hd,
        "layers": len(kinds),
        "full_layers": kinds.count("full_attention"),
        "window_layers": kinds.count("sliding_attention"),
        # parameters a token multiplies by, per layer
        "attention": d * h * hd + 2 * d * g * hd + h * hd * d,
        "router": d * doc.get("published", doc)["num_experts"],
        "expert": 3 * d * doc["moe_intermediate_size"],
        "head": d * doc["vocab_size"],
        # values of one cached token in one layer: a key and a value per head
        "row": 2 * g * hd,
    }


def always_read_params(doc: dict) -> float:
    """Parameters every decode step reads whatever the batch: attention and
    router of every layer, the head."""
    s = shapes(doc)
    return s["layers"] * (s["attention"] + s["router"]) + s["head"]


def token_linear_flops(doc: dict, *, head: bool) -> float:
    """Matmul FLOPs of one token outside attention's scores and the experts
    (those are counted from the counters)."""
    s = shapes(doc)
    return 2.0 * (s["layers"] * (s["attention"] + s["router"])
                  + (s["head"] if head else 0))


def pair_flops(doc: dict) -> float:
    """One (token, expert) pair through one expert."""
    return 2.0 * shapes(doc)["expert"]


def attention_flops(doc: dict, full_pairs: float, window_pairs: float) -> float:
    """Scores and weighted sums of every query head: per (query, key) pair
    and head ``head_dim`` multiply-adds each; ``full_pairs`` /
    ``window_pairs`` are ONE layer's pairs inside the mask, of each kind."""
    s = shapes(doc)
    pairs = full_pairs * s["full_layers"] + window_pairs * s["window_layers"]
    return 2.0 * pairs * s["heads"] * 2 * s["head_dim"]


def kv_bytes(doc: dict, full_keys: float, window_keys: float,
             itemsize: int = 2) -> float:
    """Cached keys and values read: ``*_keys`` are ONE layer's keys, of each
    kind."""
    s = shapes(doc)
    keys = full_keys * s["full_layers"] + window_keys * s["window_layers"]
    return float(keys) * s["row"] * itemsize


def decode_step(doc: dict, *, rows: float, full_keys: float,
                window_keys: float, experts_touched: float, pairs: float,
                itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of ONE decode step of ``rows`` live rows that read
    ``full_keys`` / ``window_keys`` cached keys in a layer of each kind,
    touched ``experts_touched`` experts (summed over the layers) with
    ``pairs`` (token, expert) pairs."""
    s = shapes(doc)
    flops = (rows * token_linear_flops(doc, head=True)
             + pairs * pair_flops(doc)
             + attention_flops(doc, full_keys, window_keys))
    bytes_ = (itemsize * (always_read_params(doc)
                          + experts_touched * s["expert"])
              + kv_bytes(doc, full_keys, window_keys, itemsize))
    return flops, bytes_
