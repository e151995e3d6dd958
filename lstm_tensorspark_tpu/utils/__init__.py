from .tracing import Tracer, get_tracer, set_tracer, span
from .flops import (
    PEAK_BF16_TFLOPS,
    TRAIN_FLOPS_MULTIPLIER,
    classifier_fwd_flops_per_token,
    lm_fwd_flops_per_token,
    seq2seq_fwd_flops_per_seq,
)

__all__ = [
    "Tracer", "get_tracer", "set_tracer", "span",
    "PEAK_BF16_TFLOPS", "TRAIN_FLOPS_MULTIPLIER",
    "classifier_fwd_flops_per_token", "lm_fwd_flops_per_token",
    "seq2seq_fwd_flops_per_seq",
]
