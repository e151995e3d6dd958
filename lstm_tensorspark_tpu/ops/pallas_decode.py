"""Fused Pallas TPU decode-window kernel for the serve plane.

The serve engine's windowed decode (`serve/engine.py decode_window`) is a
`lax.scan` over the fused cell + head + sampler: XLA executes K small
programs per window, each round-tripping the [B, H] carries and the
[B, V] logits through HBM, plus a gather/scatter pair at the window
boundaries. This kernel runs the WHOLE window in one `pallas_call`:

- the h/c carries of every layer stay RESIDENT IN VMEM across the K
  steps (the paper's O(1) recurrent-state thesis applied to serving:
  an LSTM session's entire decode state is [L, H] — it fits VMEM with
  room to spare, unlike a transformer's KV cache);
- the per-row EOS / budget / finished latches live in VMEM registers
  across the steps — exactly the `decode_window` latch algebra, so a
  window is always safe to run past a row's end (frozen carries, PAD
  output);
- the embedding lookup is a one-hot MXU matmul (the standard TPU
  gather-free embedding — ops/embedding.py does the same for training),
  the gates are the fused-kernel matmuls of `ops/lstm_cell.lstm_step`,
  and the head + sampler run in-kernel, so the ONLY HBM traffic per
  window is weights in (once), token block + row summary out.

**Token-identical sampling.** Greedy is an in-kernel argmax over the
f32-cast logits — bit-identical to `models/generate.sample_logits`.
Temperature sampling uses the Gumbel-argmax identity that
`jax.random.categorical` itself is built on: the (traced) wrapper draws
``gumbel(rng_k, [B, V])`` noise per step with the SAME split chain the
scan path feeds `sample_logits`, and the kernel computes
``argmax(logits/max(t, 1e-6) + noise)`` — float addition is commutative
bit-exactly, so the sampled tokens match the scan window token for
token (tests/test_pallas_decode.py). Top-k / top-p truncation would
need an in-kernel sort; those configs fall back to the scan window
(`ServeEngine` counts the fallback honestly).

**Interpreter-mode fallback**: off-TPU the kernel runs under
``interpret=True`` — the same kernel body executed by XLA on CPU — so
tier-1 proves token parity vs the scan window and `models/generate.py`
without hardware; `tests_tpu/test_pallas_decode_tpu.py` is the
compiled-Mosaic parity + perf gate. Interpreted execution is SLOWER
than the scan path (it exists for correctness coverage, not speed) —
`--decode-kernel auto` therefore resolves to ``scan`` off-TPU.

VMEM plan (`plan_fits` — the serve twin of `ops/pallas_lstm.py`'s
`_plan_fwd` accounting, same 12 MiB budget): weights (embedding, L
fused layer kernels, head) + carries + the [K, B, V] noise block
(sampled mode only) + the [B, V] logits/one-hot working set must fit;
shapes that do not (huge vocab x large batch bucket x deep window)
fall back to the scan window per compile key. docs/OPERATIONS.md
carries the budget table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: emitted for a dead row's steps — MUST equal serve/engine.py PAD_TOKEN
#: (imported there and asserted equal at engine init; kept literal here
#: so ops/ stays import-independent of serve/)
PAD_TOKEN = -1

_VMEM_BUDGET = 12 * 2**20  # bytes; conservative vs ~16 MiB/core


def sampling_supported(temperature: float, top_k, top_p, greedy: bool) -> bool:
    """Which sampling configs the kernel reproduces bit-exactly: greedy
    (in-kernel argmax) and pure temperature sampling (Gumbel-argmax with
    wrapper-drawn noise). Top-k/top-p need an in-kernel sort — those
    dispatch the scan window instead."""
    if greedy:
        return True
    return top_k is None and top_p is None


def plan_bytes(batch_b: int, window: int, num_layers: int, hidden: int,
               embed: int, vocab: int, *, sampled: bool,
               pbytes: int = 4) -> int:
    """VMEM bytes the kernel needs resident (no grid — one invocation
    holds everything). Mirrors the `ops/pallas_lstm.py` cost-model
    style: every operand + output + the [B, V] working set, counted
    once (nothing streams)."""
    v = vocab * embed * pbytes                      # embedding table
    v += (embed + (num_layers - 1) * hidden) * 4 * hidden * pbytes  # Ws
    v += num_layers * hidden * 4 * hidden * pbytes  # Us
    v += num_layers * 4 * hidden * 4                # biases (f32)
    v += hidden * vocab * pbytes + vocab * 4        # head kernel + bias
    v += 4 * num_layers * batch_b * hidden * 4      # h/c in + out
    v += window * batch_b * 4                       # token block out
    v += 4 * batch_b * 4 * 4                        # row vectors (latches)
    if sampled:
        v += window * batch_b * vocab * 4           # gumbel noise block
    # working set: one-hot + logits + gate pre-activations (live values)
    v += 2 * batch_b * vocab * 4
    v += batch_b * 4 * hidden * 4
    return v


def plan_fits(batch_b: int, window: int, num_layers: int, hidden: int,
              embed: int, vocab: int, *, sampled: bool,
              pbytes: int = 4) -> bool:
    return plan_bytes(batch_b, window, num_layers, hidden, embed, vocab,
                      sampled=sampled, pbytes=pbytes) <= _VMEM_BUDGET


def _argmax_col(x):
    """``jnp.argmax(x, axis=-1)`` as a [B, 1] int32 column: the first
    index of the row maximum (NaN counts as maximal, as in jnp.argmax).
    Built from keepdims reductions because Mosaic cannot relayout the
    1-D [B] result of an argmax into the column the latches live in."""
    m = jnp.max(x, axis=-1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    first = jnp.where((x == m) | (x != x), lane, x.shape[-1])
    return jnp.min(first, axis=-1, keepdims=True)


def _latch(emit, new, old):
    """Per-row commit: rows of the [B, 1] bool column ``emit`` take
    ``new``, the rest keep ``old``."""
    return [jnp.where(emit, n, o) for o, n in zip(old, new)]


def _model_step(tok, hs, cs, emb_ref, layer_refs, head_ref, hb_ref, *,
                vocab: int, ldtype):
    """One decode step of one model inside a kernel (the decode window's
    per-step body; the spec kernel runs it for the target AND the draft).
    ``tok`` is a [B, 1] int32 column. Returns ``(logits_f32, new_hs,
    new_cs)`` (uncommitted — the caller latches)."""
    B = tok.shape[0]
    # embedding gather as a one-hot MXU matmul (exact: 1.0 * row + zeros
    # — bit-identical to jnp.take's row copy; PAD's one-hot is all-zero)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (B, vocab), 1)
              == tok).astype(jnp.float32)
    x = jnp.dot(onehot, emb_ref[:].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    if emb_ref.dtype != jnp.float32:
        # mirror decode_one: jnp.take yields the embedding's dtype, and
        # lstm_step casts x to the kernel dtype from THERE — narrow back
        # so the downstream cast chain is identical
        x = x.astype(emb_ref.dtype)
    new_hs, new_cs = [], []
    for l, (w_ref, u_ref, b_ref) in enumerate(layer_refs):
        # ops/lstm_cell.lstm_step on fused kernels, op for op
        dtype = w_ref.dtype
        z = jnp.dot(x.astype(dtype), w_ref[:],
                    preferred_element_type=jnp.float32)
        z = z + jnp.dot(hs[l].astype(dtype), u_ref[:],
                        preferred_element_type=jnp.float32)
        z = z + b_ref[0]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f)
        g = jnp.tanh(g)
        o = jax.nn.sigmoid(o)
        c_new = f * cs[l] + i * g
        h_new = o * jnp.tanh(c_new)
        new_hs.append(h_new)
        new_cs.append(c_new)
        x = h_new
    # head (models/generate.decode_one): same dtype chain — near-tied
    # logits must argmax identically
    logits = (
        jnp.dot(x.astype(head_ref.dtype), head_ref[:],
                preferred_element_type=ldtype)
        + hb_ref[0].astype(ldtype)
    ).astype(jnp.float32)
    return logits, new_hs, new_cs


def _take_model_refs(refs, idx: int, num_layers: int):
    """``(emb_ref, layer_refs, head_ref, head_bias_ref), next_idx`` from
    the flat operand list both kernels receive."""
    emb_ref = refs[idx]
    idx += 1
    layer_refs = [tuple(refs[idx + 3 * l: idx + 3 * l + 3])
                  for l in range(num_layers)]
    idx += 3 * num_layers
    return (emb_ref, layer_refs, refs[idx], refs[idx + 1]), idx + 2


def _decode_window_kernel(*refs, num_layers: int, vocab: int,
                          window: int, temperature: float, greedy: bool,
                          ldtype):
    """One fused decode window. Carries, latches and the token block all
    live in VMEM for the K python-unrolled steps; the latch algebra is
    the scan window's, verbatim (serve/engine.py `window_fn.step`):

    - rows alive at step entry emit this step's token and commit its
      carry update (the EOS-emitting step still writes carries);
    - dead rows emit PAD_TOKEN, keep frozen carries, and feed token 0
      forward (the value never matters).

    Every per-row value (token, latches, budget) is a [B, 1] column from
    its ref to its ref: rows on sublanes, like the [B, H] carries they
    gate. Mosaic cannot reshape a 1-D [B] mask into that column.
    """
    L = num_layers
    model, idx = _take_model_refs(refs, 0, L)
    h0_ref, c0_ref, tok_ref, alive_ref, rem_ref, eos_ref = refs[idx:idx + 6]
    idx += 6
    noise_ref = None
    if not greedy:
        noise_ref = refs[idx]
        idx += 1
    (toks_ref, next_ref, alive_out_ref, rem_out_ref,
     h_out_ref, c_out_ref) = refs[idx:idx + 6]

    tok = tok_ref[...]                # [B, 1] int32
    alive = alive_ref[...] != 0       # [B, 1] bool
    rem = rem_ref[...]                # [B, 1] int32
    eos = eos_ref[...]                # [B, 1] int32 (-1 = none)
    B = tok.shape[0]
    hs = [h0_ref[l] for l in range(L)]
    cs = [c0_ref[l] for l in range(L)]
    step_lane = jax.lax.broadcasted_iota(jnp.int32, (B, window), 1)
    toks = jnp.full((B, window), PAD_TOKEN, jnp.int32)

    for k in range(window):
        logits, new_hs, new_cs = _model_step(
            tok, hs, cs, *model, vocab=vocab, ldtype=ldtype)
        if not greedy:
            if temperature != 1.0:
                logits = logits / max(temperature, 1e-6)
            # Gumbel-argmax == jax.random.categorical (float addition is
            # commutative bit-exactly; the wrapper drew noise with the
            # scan path's exact split chain)
            logits = logits + noise_ref[k]
        nxt = _argmax_col(logits)
        # the scan window's latch algebra, verbatim
        emit = alive
        out_tok = jnp.where(emit, nxt, PAD_TOKEN)
        new_rem = rem - emit.astype(rem.dtype)
        hit_eos = emit & (eos >= 0) & (nxt == eos)
        new_alive = emit & ~hit_eos & (new_rem > 0)
        hs = _latch(emit, new_hs, hs)
        cs = _latch(emit, new_cs, cs)
        tok = jnp.where(new_alive, nxt, 0)
        alive = new_alive
        rem = new_rem
        toks = jnp.where(step_lane == k, out_tok, toks)

    # the per-row summary the scheduler tick reads (one tiny readback
    # per window instead of Python bookkeeping per row)
    toks_ref[...] = toks
    next_ref[...] = tok
    alive_out_ref[...] = alive.astype(jnp.int32)
    rem_out_ref[...] = rem
    for l in range(L):
        h_out_ref[l] = hs[l].astype(jnp.float32)
        c_out_ref[l] = cs[l].astype(jnp.float32)


def _model_operands(params, fused_layers, cfg):
    """One model's weights in `_take_model_refs` order."""
    V, E = cfg.vocab_size, cfg.embed
    # E is only consulted by plan_fits; checked here so a config whose
    # layer-0 width disagrees with the embedding table fails loudly at
    # trace time instead of producing shape errors inside the kernel
    if params["embedding"].shape != (V, E):
        raise ValueError(
            f"embedding table {params['embedding'].shape} != {(V, E)}")
    head = params["head"]
    head_kernel = (params["embedding"].T if cfg.tie_embeddings
                   else head["kernel"])
    operands = [params["embedding"]]
    for fused in fused_layers:
        operands += [fused.kernel, fused.recurrent,
                     fused.bias.reshape(1, -1)]
    return operands + [head_kernel, head["bias"].reshape(1, -1)]


def _col(x):
    """A per-row [B] vector as the [B, 1] int32 column the kernels use."""
    return x.reshape(-1, 1).astype(jnp.int32)


def decode_window_call(params, fused_layers, cfg, h_in, c_in, tokens,
                       alive, remaining, eos_ids, noise, *, window: int,
                       temperature: float, greedy: bool,
                       interpret: bool):
    """Trace-level entry (called inside the engine's jitted wrapper):
    run one fused decode window over the GATHERED carries.

    ``h_in``/``c_in`` [L, B, H] f32; ``tokens``/``remaining``/``eos_ids``
    [B] int32; ``alive`` [B] bool; ``noise`` [K, B, V] f32 gumbel draws
    (None when greedy). Returns ``(h_out, c_out, toks [B, K] int32,
    next_tok [B] int32, alive_out [B] bool, rem_out [B] int32)`` — the
    exact shapes/dtypes the scan window produces, so the two kernels are
    interchangeable behind one `DecodeWindow`."""
    L, B, H = h_in.shape
    operands = _model_operands(params, fused_layers, cfg) + [
        h_in, c_in, _col(tokens), _col(alive), _col(remaining),
        _col(eos_ids)]
    if not greedy:
        operands.append(noise)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    col = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    carry = jax.ShapeDtypeStruct((L, B, H), jnp.float32)
    out_shape = [
        jax.ShapeDtypeStruct((B, window), jnp.int32),   # token block
        col, col, col,          # next token, alive summary, remaining
        carry, carry,           # h out, c out
    ]
    toks, next_tok, alive_out, rem_out, h_out, c_out = pl.pallas_call(
        functools.partial(
            _decode_window_kernel, num_layers=L, vocab=cfg.vocab_size,
            window=window, temperature=temperature, greedy=greedy,
            ldtype=cfg.ldtype,
        ),
        in_specs=[vmem] * len(operands),
        out_specs=[vmem] * len(out_shape),
        out_shape=out_shape,
        interpret=interpret,
        name="decode_window",
    )(*operands)
    return (h_out, c_out, toks, next_tok[:, 0],
            alive_out[:, 0].astype(bool), rem_out[:, 0])


# ---- speculative verify window (draft + target, fused) -----------------


def spec_plan_bytes(batch_b: int, k_draft: int, num_layers: int,
                    hidden: int, embed: int, vocab: int,
                    draft_layers: int, draft_hidden: int,
                    draft_embed: int, *, pbytes: int = 4) -> int:
    """VMEM plan for the fused spec window: BOTH models' weights and
    carries are resident for the whole propose+verify pass. Composed
    from two greedy `plan_bytes` plans (target at W = K+1, draft
    likewise — the draft runs every verify step teacher-forced) plus
    the proposal block; the double-counted [B, V] working set is kept
    as slack (the two models step sequentially, so the true live set is
    smaller — overcounting only ever falls back to the scan window)."""
    w = k_draft + 1
    v = plan_bytes(batch_b, w, num_layers, hidden, embed, vocab,
                   sampled=False, pbytes=pbytes)
    v += plan_bytes(batch_b, w, draft_layers, draft_hidden, draft_embed,
                    vocab, sampled=False, pbytes=pbytes)
    v += k_draft * batch_b * 4  # proposal block
    return v


def spec_plan_fits(batch_b: int, k_draft: int, num_layers: int,
                   hidden: int, embed: int, vocab: int,
                   draft_layers: int, draft_hidden: int,
                   draft_embed: int, *, pbytes: int = 4) -> bool:
    return spec_plan_bytes(
        batch_b, k_draft, num_layers, hidden, embed, vocab,
        draft_layers, draft_hidden, draft_embed,
        pbytes=pbytes) <= _VMEM_BUDGET


def _spec_window_kernel(*refs, num_layers: int, draft_layers: int,
                        vocab: int, k_draft: int, ldtype, dldtype):
    """The fused speculative step, greedy-only. Phase 1: the draft
    decodes ``k_draft`` proposals from its VMEM-resident carries (the
    propose-time carries are discarded). Phase 2: ``W = k_draft + 1``
    joint verify steps run the TARGET teacher-forced over [last_token,
    proposals...] with the DRAFT stepping alongside on the same inputs;
    both models' carries latch on the scan spec window's exact ``emit``
    mask (serve/engine.py `_get_spec_window_fn`), the emitted prefix is
    the plain greedy sequence by construction, and the disagreement-
    detecting step emits the target's own argmax as the correction
    token. The returned ``alive`` is the SESSION latch (EOS/budget) —
    a draft miss ends the window, never the conversation. Per-row
    values are [B, 1] columns, as in `_decode_window_kernel`."""
    L, Ld = num_layers, draft_layers
    target, idx = _take_model_refs(refs, 0, L)
    draft, idx = _take_model_refs(refs, idx, Ld)
    (h0_ref, c0_ref, dh0_ref, dc0_ref,
     tok_ref, alive_ref, rem_ref, eos_ref) = refs[idx:idx + 8]
    (toks_ref, next_ref, alive_out_ref, rem_out_ref,
     h_out_ref, c_out_ref, dh_out_ref, dc_out_ref) = refs[idx + 8:idx + 16]

    tok = tok_ref[...]                # [B, 1] int32
    alive = alive_ref[...] != 0       # [B, 1] bool — window latch, step 0
    rem = rem_ref[...]                # [B, 1] int32
    eos = eos_ref[...]                # [B, 1] int32 (-1 = none)
    B = tok.shape[0]
    W = k_draft + 1
    hs = [h0_ref[l] for l in range(L)]
    cs = [c0_ref[l] for l in range(L)]
    dhs0 = [dh0_ref[l] for l in range(Ld)]
    dcs0 = [dc0_ref[l] for l in range(Ld)]

    # phase 1 — draft proposes K greedy tokens; its propose-time carries
    # are discarded (the verify phase re-runs the draft teacher-forced,
    # which is the state commit)
    props = []
    dhs, dcs = list(dhs0), list(dcs0)
    ptok = tok
    for _ in range(k_draft):
        dlogits, dhs, dcs = _model_step(
            ptok, dhs, dcs, *draft, vocab=vocab, ldtype=dldtype)
        ptok = _argmax_col(dlogits)
        props.append(ptok)

    # phase 2 — W joint teacher-forced verify steps
    dhs, dcs = list(dhs0), list(dcs0)
    sess_alive = alive
    final_tok = tok
    step_lane = jax.lax.broadcasted_iota(jnp.int32, (B, W), 1)
    toks = jnp.full((B, W), PAD_TOKEN, jnp.int32)
    for i in range(W):
        inp = tok if i == 0 else props[i - 1]
        logits, new_hs, new_cs = _model_step(
            inp, hs, cs, *target, vocab=vocab, ldtype=ldtype)
        _, new_dhs, new_dcs = _model_step(
            inp, dhs, dcs, *draft, vocab=vocab, ldtype=dldtype)
        t = _argmax_col(logits)
        emit = alive
        out_tok = jnp.where(emit, t, PAD_TOKEN)
        new_rem = rem - emit.astype(rem.dtype)
        hit_eos = emit & (eos >= 0) & (t == eos)
        live_on = ~hit_eos & (new_rem > 0)
        # (a select between two masks is one Mosaic cannot lower)
        sess_alive = (emit & live_on) | (~emit & sess_alive)
        if i < k_draft:
            agree = props[i] == t
            alive = emit & live_on & agree
        else:
            # past the last proposal nothing can agree — the window
            # always closes here (the scan fn's -2 sentinel)
            alive = jnp.zeros_like(emit)
        hs = _latch(emit, new_hs, hs)
        cs = _latch(emit, new_cs, cs)
        dhs = _latch(emit, new_dhs, dhs)
        dcs = _latch(emit, new_dcs, dcs)
        final_tok = jnp.where(emit, t, final_tok)
        rem = new_rem
        toks = jnp.where(step_lane == i, out_tok, toks)

    toks_ref[...] = toks
    next_ref[...] = jnp.where(sess_alive, final_tok, 0)
    alive_out_ref[...] = sess_alive.astype(jnp.int32)
    rem_out_ref[...] = rem
    for l in range(L):
        h_out_ref[l] = hs[l].astype(jnp.float32)
        c_out_ref[l] = cs[l].astype(jnp.float32)
    for l in range(Ld):
        dh_out_ref[l] = dhs[l].astype(jnp.float32)
        dc_out_ref[l] = dcs[l].astype(jnp.float32)


def spec_window_call(params, fused_layers, cfg, dparams, dfused_layers,
                     dcfg, h_in, c_in, dh_in, dc_in, tokens, alive,
                     remaining, eos_ids, *, k_draft: int, interpret: bool):
    """Trace-level entry for the fused spec window (called inside the
    engine's jitted wrapper). ``h_in``/``c_in`` [L, B, H] f32 target
    carries, ``dh_in``/``dc_in`` [L_d, B, H_d] f32 draft carries; row
    vectors as in `decode_window_call`. Returns ``(h_out, c_out,
    dh_out, dc_out, toks [B, W] int32, next_tok [B] int32, alive_out
    [B] bool, rem_out [B] int32)`` — the scan spec fn's exact shapes,
    so the two programs are interchangeable behind one spec
    `DecodeWindow`."""
    L, B, H = h_in.shape
    Ld, _, Hd = dh_in.shape
    W = k_draft + 1
    operands = (_model_operands(params, fused_layers, cfg)
                + _model_operands(dparams, dfused_layers, dcfg)
                + [h_in, c_in, dh_in, dc_in, _col(tokens), _col(alive),
                   _col(remaining), _col(eos_ids)])
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    col = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    carry = jax.ShapeDtypeStruct((L, B, H), jnp.float32)
    dcarry = jax.ShapeDtypeStruct((Ld, B, Hd), jnp.float32)
    out_shape = [
        jax.ShapeDtypeStruct((B, W), jnp.int32),        # token block
        col, col, col,          # next token, session alive, remaining
        carry, carry, dcarry, dcarry,
    ]
    (toks, next_tok, alive_out, rem_out,
     h_out, c_out, dh_out, dc_out) = pl.pallas_call(
        functools.partial(
            _spec_window_kernel, num_layers=L, draft_layers=Ld,
            vocab=cfg.vocab_size, k_draft=k_draft,
            ldtype=cfg.ldtype, dldtype=dcfg.ldtype,
        ),
        in_specs=[vmem] * len(operands),
        out_specs=[vmem] * len(out_shape),
        out_shape=out_shape,
        interpret=interpret,
        name="spec_window",
    )(*operands)
    return (h_out, c_out, dh_out, dc_out, toks, next_tok[:, 0],
            alive_out[:, 0].astype(bool), rem_out[:, 0])
