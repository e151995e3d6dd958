"""Model-FLOPs accounting for runtime logging (--log-flops).

Matmul-only counts (the MXU work; embedding gathers and elementwise ops
are excluded, matching standard MFU practice). Training ≈ 3× forward:
the backward pass does ~2× the forward matmul work (dL/dW and dL/dx per
matmul).
"""

from __future__ import annotations

# bf16 peak TFLOP/s of ONE chip, keyed by `jax.Device.device_kind`. A
# device that is not here has no MFU: --log-flops says so and reports
# model_tflops alone; a benchmark treats it as an error, never a default.
PEAK_BF16_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197.0,
}

# fwd + bwd(2x) matmul accounting
TRAIN_FLOPS_MULTIPLIER = 3.0


def lm_fwd_flops_per_token(V: int, H: int, L: int,
                           E: int | None = None) -> float:
    """Matmul-only forward FLOPs per token: per layer x@W (2*Din*4H) +
    h@U (2*H*4H), plus the softmax head (2*H*V). Embedding gather ~0."""
    E = E or H
    f = 0.0
    for layer in range(L):
        din = E if layer == 0 else H
        f += 8.0 * H * (din + H)
    return f + 2.0 * H * V


def classifier_fwd_flops_per_token(V: int, H: int, L: int,
                                   E: int | None = None) -> float:
    """Bi-LSTM: two directions per layer; layer 0 input E, later 2H.
    The [2H, C] head is per-sequence and negligible."""
    E = E or H
    f = 0.0
    for layer in range(L):
        din = E if layer == 0 else 2 * H
        f += 2 * 8.0 * H * (din + H)
    return f


def seq2seq_fwd_flops_per_seq(F: int, H: int, L: int, T: int,
                              horizon: int) -> float:
    """Encoder over T context steps + teacher-forced decoder over the
    horizon + per-step projection [H, F]."""
    enc = dec = 0.0
    for layer in range(L):
        din = F if layer == 0 else H
        enc += 8.0 * H * (din + H)
        dec += 8.0 * H * (din + H)
    return T * enc + horizon * (dec + 2.0 * H * F)


def decoder_fwd_flops_per_token(cfg, *, context: int = 0,
                                pairs_here: float | None = None,
                                head: bool = True) -> float:
    """Matmul-only forward FLOPs of ONE token through a decoder
    (`models.decoder.DecoderConfig`) as computed on this chip: every layer's
    attention projections, the dense or shared MLP and the router, the
    routed experts for ``pairs_here`` (token, expert) pairs a layer that
    land on the experts held (default: the held share of top-k, as an even
    router gives), the absorbed attention over ``context`` cached tokens,
    and the head over the vocabulary slice."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    kv, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    attention = (d * cfg.q_lora_rank + cfg.q_lora_rank * h * qd
                 + d * (kv + rope)
                 + kv * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
                 + h * cfg.v_head_dim * d)
    dense = cfg.first_k_dense_replace
    moe = cfg.num_hidden_layers - dense
    if pairs_here is None:
        pairs_here = (cfg.num_experts_per_tok * cfg.experts_held
                      / cfg.n_routed_experts)
    expert = 3 * d * cfg.moe_intermediate_size
    params = (cfg.num_hidden_layers * attention
              + dense * 3 * d * cfg.intermediate_size
              + moe * (cfg.n_shared_experts * expert
                       + d * cfg.n_routed_experts + pairs_here * expert)
              + (d * cfg.vocab_size if head else 0))
    scores = cfg.num_hidden_layers * context * h * (kv + rope + kv)
    return 2.0 * (params + scores)
