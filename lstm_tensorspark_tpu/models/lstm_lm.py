"""LSTM language model: embedding → stacked LSTM → softmax head.

Reference parity: SURVEY.md §2 "Multi-layer / network wrapper" [P] — stacks
cells over layers, unrolls over time, projection + softmax head,
cross-entropy loss. Covers BASELINE.md configs 1 (PTB char, 1×128),
3 (WikiText-2 word, 2×650) and 5 (WikiText-103, 4×1024) by hyperparameters.

Params are a plain pytree (dict of arrays / LSTMParams), the step is a pure
function — this is what lets the same code run under jit, grad, shard_map and
the multi-chip dry-run without modification.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.embedding import embed_lookup
from ..ops.lstm_cell import LSTMParams, init_lstm_params, zero_carry
from ..ops.scan import stacked_lstm_scan
from ..ops.xent import chunked_xent_mean, dense_xent_mean

# Above this vocab size lm_loss switches from the dense head + loss
# (ops/xent.py dense_xent_mean: the whole [N,V] logits array in HBM) to the
# vocab-chunked cross-entropy (chunked_xent_mean), which bounds loss memory
# at O(N·Vc) instead of O(N·V).
# MEASURED on v5e: at V=33k/50k the chunked path is 16-18% SLOWER than the
# dense one (XLA already fuses the head matmul + reduction well; the scan
# serializes chunk matmuls and doubles the exp work), so the threshold sits
# ABOVE those configs — the chunked path is a memory capability for
# vocabularies whose [N,V] logits would not fit HBM, not a throughput
# optimisation.
_CHUNKED_XENT_MIN_V = 2**17


@dataclasses.dataclass(frozen=True)
class LMConfig:
    vocab_size: int
    hidden_size: int = 128
    num_layers: int = 1
    embed_size: int | None = None  # defaults to hidden_size
    dropout: float = 0.0
    tie_embeddings: bool = False
    compute_dtype: str = "float32"  # "bfloat16" for MXU-friendly matmuls
    remat_chunk: int | None = None
    scan_unroll: int = 1
    # fused Pallas recurrence kernel (ops/pallas_lstm.py) when shapes/platform
    # allow; falls back to lax.scan per layer otherwise
    use_pallas: bool = False
    # BPTT mode for the recurrence (ops/parallel_scan.py): "sequential",
    # "assoc" (parallel-scan backward), or "auto" (assoc when the memory
    # plan fits and T is long enough). Library default stays sequential;
    # `cli train --bptt-mode` defaults to auto.
    bptt: str = "sequential"
    # dtype of the materialized [N,V] logits array (N = B·T). At the
    # word-LM vocab sizes every pass over that array is an HBM-bandwidth
    # cost. On a TPU (ops/xent.py dense_xent_mean, ops/pallas_xent.py) the
    # head kernel writes it with its logsumexp and target logit, and the
    # backward reads it twice: the dx kernel (which also sums the bias
    # gradient) and XLA's weight-gradient matmul, each forming dlogits on
    # the fly — no dlogits array is stored, and none is copied into a
    # second layout (819 MB each at config 5). Where the kernels do not run
    # (another backend, H off the 128 lanes, a head stored row-major, an
    # automatic mesh axis around the call, a head the data-parallel step
    # gathers) XLA reads it four times:
    # logsumexp + target logit, the bias gradient, and each backward
    # matmul. "bfloat16" halves all of them (+25% measured on config 3
    # with the autodiff backward) while the logsumexp/NLL itself still
    # runs in f32 over the upcast values; it is also the dtype dlogits is
    # rounded to.
    # Default float32 — opt-in numerics trade. No effect on the
    # chunked-xent path (V >= _CHUNKED_XENT_MIN_V), which never
    # materializes the array this flag exists to shrink.
    logits_dtype: str = "float32"

    @property
    def embed(self) -> int:
        return self.embed_size or self.hidden_size

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def ldtype(self):
        return jnp.dtype(self.logits_dtype)


def init_lm(key: jax.Array, cfg: LMConfig):
    """Initialize the LM parameter pytree."""
    if cfg.tie_embeddings and cfg.embed != cfg.hidden_size:
        raise ValueError("tie_embeddings requires embed_size == hidden_size")
    keys = jax.random.split(key, cfg.num_layers + 2)
    embedding = (
        jax.random.normal(keys[0], (cfg.vocab_size, cfg.embed)) * 0.02
    ).astype(jnp.float32)
    layers = []
    for i in range(cfg.num_layers):
        in_size = cfg.embed if i == 0 else cfg.hidden_size
        layers.append(init_lstm_params(keys[1 + i], in_size, cfg.hidden_size))
    params = {"embedding": embedding, "layers": layers}
    if not cfg.tie_embeddings:
        params["head"] = {
            "kernel": jax.nn.initializers.glorot_uniform()(
                keys[-1], (cfg.hidden_size, cfg.vocab_size), jnp.float32
            ),
            "bias": jnp.zeros((cfg.vocab_size,), jnp.float32),
        }
    else:
        params["head"] = {"bias": jnp.zeros((cfg.vocab_size,), jnp.float32)}
    return params


def init_carries(cfg: LMConfig, batch: int):
    return [zero_carry(batch, cfg.hidden_size) for _ in range(cfg.num_layers)]


def lm_backbone(
    params,
    tokens: jax.Array,
    cfg: LMConfig,
    *,
    carries=None,
    mask: jax.Array | None = None,
    dropout_rng: jax.Array | None = None,
    deterministic: bool = True,
):
    """tokens [B, T] int32 → (per-layer final carries, pre-head
    activations [B, T, H]).

    ``mask`` [B, T] bool (optional) freezes the recurrent carries at False
    steps (ops/scan.py), so right-padded batches end with each row's true
    final state — the serving engine's bucket-padded prefill (serve/).
    """
    cdtype = cfg.cdtype
    # embed_lookup: gather forward; at small V the gradient is an MXU
    # matmul, not a scatter (ops/embedding.py — measured 28 us/step saved
    # at the config-1 shape)
    xs = embed_lookup(params["embedding"], tokens)
    return stacked_lstm_scan(
        params["layers"],
        xs,
        carries,
        mask=mask,
        dropout_rate=cfg.dropout,
        dropout_rng=dropout_rng,
        deterministic=deterministic,
        compute_dtype=None if cdtype == jnp.float32 else cdtype,
        remat_chunk=cfg.remat_chunk,
        unroll=cfg.scan_unroll,
        use_pallas=cfg.use_pallas,
        bptt=cfg.bptt,
    )


def _head_kernel(params, cfg: LMConfig):
    head = params["head"]
    kernel = params["embedding"].T if cfg.tie_embeddings else head["kernel"]
    return kernel, head["bias"]


def lm_forward(
    params,
    tokens: jax.Array,
    cfg: LMConfig,
    *,
    carries=None,
    dropout_rng: jax.Array | None = None,
    deterministic: bool = True,
):
    """tokens [B, T] int32 → (logits [B, T, V], final per-layer carries)."""
    finals, ys = lm_backbone(
        params, tokens, cfg, carries=carries, dropout_rng=dropout_rng,
        deterministic=deterministic,
    )
    kernel, bias = _head_kernel(params, cfg)
    logits = (
        jnp.dot(ys.astype(kernel.dtype), kernel,
                preferred_element_type=cfg.ldtype)
        + bias.astype(cfg.ldtype)
    )
    return logits, finals


def lm_loss(
    params,
    batch,
    cfg: LMConfig,
    *,
    carries=None,
    dropout_rng=None,
    deterministic: bool = True,
):
    """Next-token cross-entropy (mean over B*T tokens), as in the reference's
    ``xent(softmax(h·W_out), y)`` head (SURVEY.md §3.2).

    batch: dict with "inputs" [B,T] and "targets" [B,T] int32.
    Returns (loss, aux) with aux = {"loss", "tokens", "carries"}.
    """
    finals, ys = lm_backbone(
        params, batch["inputs"], cfg, carries=carries,
        dropout_rng=dropout_rng, deterministic=deterministic,
    )
    kernel, bias = _head_kernel(params, cfg)
    if cfg.vocab_size >= _CHUNKED_XENT_MIN_V:
        # big-vocab path: vocab-chunked cross-entropy (ops/xent.py) — the
        # [B,T,V] logits/dlogits arrays never exist in HBM; head matmul
        # recomputed chunk-wise in the backward
        loss = chunked_xent_mean(ys.astype(jnp.float32), kernel, bias,
                                 batch["targets"])
    else:
        # dense path: lm_forward's head, then nll = lse - z_t, with a
        # backward that needs dlogits in one layout only (ops/xent.py)
        loss = dense_xent_mean(ys, kernel, bias, batch["targets"], cfg.ldtype)
    aux = {
        "loss": loss,
        "tokens": jnp.array(batch["targets"].size, jnp.float32),
        "carries": finals,
    }
    return loss, aux
