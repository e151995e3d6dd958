"""Pipeline parallelism for stacked LSTM layers over the "pipe" mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2 strategy inventory:
"not required for parity") — this is new first-class capability, built on
the same wavefront machinery as sequence parallelism (DESIGN.md notes the
wavefront is PP's natural substrate).

Layout: the L stacked layers are split into S = |pipe| stages of L/S layers
each; stage s owns layers [s*L/S, (s+1)*L/S). Layer parameters (and their
optimizer state) are *sharded* over "pipe" — each device stores only its
stage's weights, the point of PP. Embedding and head are replicated; only
stage 0 reads the embedding and only stage S-1 applies the head, so their
gradients are nonzero on exactly one stage and shard_map's transpose psums
them back to consistency.

Schedule: GPipe-style wavefront over M microbatches — at tick t, stage s
processes microbatch m = t - s and hands its activations [b, T, H] one hop
right via `lax.ppermute` (ICI neighbor traffic only). Utilization is
M/(M+S-1): the (S-1)-tick fill/drain bubble amortises away as M grows.
`lax.cond` on the per-device active predicate skips real compute during
bubble ticks (safe here: no collectives inside a stage's scan).

Autodiff: `jax.grad` through the shard_map reverses the wavefront
(ppermute transposes to the opposite ring), giving pipelined BPTT with the
same schedule in reverse. The train step does grad/update at the jit level —
shard_map's transpose inserts the psums for replicated inputs, and GSPMD
propagates the P("pipe") param sharding to the optimizer state, so each
stage's Adam moments etc. also live only on that stage.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..models.lstm_lm import LMConfig
from ..ops.embedding import embed_lookup
from ..ops.lstm_cell import LSTMParams
from ..ops.scan import auto_lstm_scan, lstm_scan
from ..ops.xent import dense_xent_mean
from ..train.loop import TrainState, step_body


def stack_layers(layers: list[LSTMParams]) -> LSTMParams:
    """Stack per-layer params into one LSTMParams of [L, ...] arrays so the
    layer axis can be sharded over "pipe".

    Non-uniform input sizes (embed_size != hidden_size makes layer 0's W
    rows differ) are zero-PADDED to the max input size. Padding is exact:
    the padded W rows multiply zero-padded activations (pp_lm_loss pads its
    inter-layer tensors), contribute nothing to the forward, and receive
    identically-zero gradients (dW_pad = x_pad^T @ dz = 0), so they stay
    zero under any optax transform."""
    dmax = max(p.input_size for p in layers)

    def pad_w(p: LSTMParams) -> LSTMParams:
        pad = dmax - p.input_size
        if pad == 0:
            return p
        pw = lambda a: jnp.pad(a, ((0, pad), (0, 0)))
        return p._replace(W_i=pw(p.W_i), W_f=pw(p.W_f),
                          W_g=pw(p.W_g), W_o=pw(p.W_o))

    return jax.tree.map(lambda *a: jnp.stack(a), *[pad_w(p) for p in layers])


def unstack_layers(
    stacked: LSTMParams, input_sizes: list[int] | None = None
) -> list[LSTMParams]:
    """Invert stack_layers; ``input_sizes`` slices each layer's W back to
    its true row count (None = uniform stack, no slicing)."""
    L = stacked.W_i.shape[0]
    layers = [jax.tree.map(lambda a: a[j], stacked) for j in range(L)]
    if input_sizes is None:
        return layers

    def cut(p: LSTMParams, d: int) -> LSTMParams:
        cw = lambda a: a[:d]
        return p._replace(W_i=cw(p.W_i), W_f=cw(p.W_f),
                          W_g=cw(p.W_g), W_o=cw(p.W_o))

    return [cut(p, d) for p, d in zip(layers, input_sizes)]


def stack_lm_params(params):
    """LM params with the per-layer list replaced by a stacked pytree."""
    return {**params, "layers": stack_layers(params["layers"])}


def unstack_lm_params(params):
    """Invert stack_lm_params, recovering the true per-layer W row counts
    (layer 0: embed dim from the embedding table; rest: hidden)."""
    embed = params["embedding"].shape[1]
    hidden = params["layers"].U_i.shape[-1]
    L = params["layers"].W_i.shape[0]
    sizes = [embed] + [hidden] * (L - 1)
    return {**params, "layers": unstack_layers(params["layers"], sizes)}


def pp_lm_param_specs(params_stacked):
    """shard_map in_specs: stacked layers sharded over "pipe" (the MANUAL
    axis), everything else replicated. TP does not appear here — "model" is
    an AUTO axis handled by GSPMD from the jit-level shardings below."""
    specs = {
        k: jax.tree.map(lambda _: P(), v)
        for k, v in params_stacked.items()
        if k != "layers"
    }
    specs["layers"] = jax.tree.map(lambda _: P("pipe"), params_stacked["layers"])
    return specs


def pp_lm_param_shardings(params_stacked, *, tp: bool = False):
    """jit-level PartitionSpecs: layers over "pipe" and (with ``tp``) gate/
    hidden dims over "model" — the hybrid manual-PP/auto-TP composition.
    Stacked layer arrays are [L, D, 4H] (W), [L, H, 4H] (U), [L, 4H] (b)."""
    model = "model" if tp else None
    mat = P("pipe", None, model)
    vec = P("pipe", model)
    layer_specs = LSTMParams(
        W_i=mat, W_f=mat, W_g=mat, W_o=mat,
        U_i=mat, U_f=mat, U_g=mat, U_o=mat,
        b_i=vec, b_f=vec, b_g=vec, b_o=vec,
    )
    specs = {"embedding": P(), "layers": layer_specs}
    head = {"bias": P()}
    if "kernel" in params_stacked["head"]:
        head["kernel"] = P(model, None)  # [H/P, V] row-parallel
    specs["head"] = head
    return specs


def place_pp_lm_params(params_stacked, mesh: Mesh, *, tp: bool = False):
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        params_stacked,
        pp_lm_param_shardings(params_stacked, tp=tp),
    )


def pp_zero1_opt_specs(optimizer, params_stacked, mesh: Mesh, *,
                       tp: bool = False):
    """The ZeRO-1 x PP optimizer-state spec tree — the ONE derivation
    every consumer shares (the train step's shardings pin, the CLI's and
    dryrun's initial placement, tests): each moment leaf's stage-sharded
    spec extended with the data axis (zero.zero1_tp_opt_specs applied to
    the stacked param specs)."""
    from .zero import zero1_tp_opt_specs

    return zero1_tp_opt_specs(
        optimizer, params_stacked,
        pp_lm_param_shardings(params_stacked, tp=tp), mesh,
    )


def place_pp_zero1_opt_state(opt_state, optimizer, params_stacked,
                             mesh: Mesh, *, tp: bool = False):
    """Place a fresh/restored optimizer state on its stage x data shards
    up front — no device ever materializes a data-replicated copy."""
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        opt_state,
        pp_zero1_opt_specs(optimizer, params_stacked, mesh, tp=tp),
        is_leaf=lambda x: isinstance(x, jax.Array) or x is None,
    )


def pp_lm_loss(
    params,
    batch,
    cfg: LMConfig,
    *,
    microbatches: int = 1,
    pipe_axis: str = "pipe",
    data_axis: str = "data",
    dropout_rng: jax.Array | None = None,
    uniform: bool = False,
    use_pallas: bool = False,
):
    """Global-mean LM loss under the pipeline wavefront.

    MUST run inside shard_map, manual over {pipe_axis, data_axis}. ``params``
    is the local view: layers [L/S, ...] (this stage's slice), embedding and
    head full. ``batch`` is this data-shard's {"inputs","targets"} [B_local,
    T], replicated over "pipe". Returns the already-reduced global scalar.

    embed_size != hidden_size is handled by the stack_layers zero-padding:
    every inter-layer/inter-stage tensor is carried at width
    Dmax = max(embed, hidden) with exact zero lanes (see stack_layers).

    With ``dropout_rng`` set and cfg.dropout > 0, inter-layer dropout
    applies after every layer except the globally-last one, with masks
    independent per (data shard, microbatch, layer) — the same fold-in
    scheme the DP backend uses for per-shard dropout.

    ``uniform=True`` (REQUIRED when "model" is an auto TP axis): every
    stage computes every tick and bubble results are masked with where()
    instead of skipped with lax.cond — GSPMD-inserted TP collectives must
    execute in lockstep across devices, and divergent cond branches would
    deadlock them (the same constraint as sp_lstm_scan's uniform mode).

    ``use_pallas`` runs each stage-interior recurrence through the fused
    Pallas kernel (ops/pallas_lstm.py) — legal because a stage's scan
    contains NO collectives (the only inter-device traffic is the ppermute
    between ticks), so the kernel sits entirely inside this device's manual
    shard. Callers must keep it off when "model" is an auto TP axis: GSPMD
    cannot partition a pallas_call over the sharded hidden dim.
    """
    S = lax.axis_size(pipe_axis)
    s = lax.axis_index(pipe_axis)
    M = microbatches
    inputs, targets = batch["inputs"], batch["targets"]
    B, T = inputs.shape
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    b = B // M
    H = cfg.hidden_size
    Dmax = max(cfg.embed, H)

    embedding = params["embedding"]
    head = params["head"]
    kernel = embedding.T if cfg.tie_embeddings else head["kernel"]
    local_layers = unstack_layers(params["layers"])  # padded widths kept
    n_local = len(local_layers)
    cdtype = None if cfg.cdtype == jnp.float32 else cfg.cdtype
    L_total = n_local * S
    use_dropout = dropout_rng is not None and cfg.dropout > 0.0
    if use_dropout:
        # distinct masks per data shard; pipe/microbatch/layer fold below
        dropout_rng = jax.random.fold_in(dropout_rng, lax.axis_index(data_axis))

    inputs_m = inputs.reshape(M, b, T)
    targets_m = targets.reshape(M, b, T)

    def pad_d(x):
        """[b, T, d] -> [b, T, Dmax] with exact zero lanes."""
        d = x.shape[-1]
        return x if d == Dmax else jnp.pad(x, ((0, 0), (0, 0), (0, Dmax - d)))

    def run_stage(src, rng):
        ys = src  # [b, T, Dmax]
        for i, layer in enumerate(local_layers):
            _, ys = auto_lstm_scan(
                layer, ys,
                compute_dtype=cdtype,
                remat_chunk=cfg.remat_chunk,
                unroll=cfg.scan_unroll,
                use_pallas=use_pallas,
            )
            g = s * n_local + i  # global layer index (traced: s is an
            # axis_index, so gate "not the last layer" with where, not if)
            if use_dropout:
                from ..ops.masking import dropout_with_key

                dropped = dropout_with_key(
                    jax.random.fold_in(rng, i), cfg.dropout, ys
                )
                ys = jnp.where(g == L_total - 1, ys, dropped)
            ys = pad_d(ys)
        return ys  # [b, T, Dmax]

    def mb_loss(ys, tgt):
        # lm_loss's dense head + loss (ops/xent.py) on one microbatch
        return dense_xent_mean(ys[..., :H], kernel, head["bias"], tgt,
                               cfg.ldtype)

    x_in = jnp.zeros((b, T, Dmax), jnp.float32)
    loss_acc = jnp.zeros((), jnp.float32)
    right = [(i, i + 1) for i in range(S - 1)]  # linear chain, no wraparound
    is_last = s == S - 1

    for t in range(M + S - 1):
        m = t - s  # microbatch this stage works on at tick t
        active = jnp.logical_and(m >= 0, m < M)
        m_c = jnp.clip(m, 0, M - 1)
        tok = lax.dynamic_index_in_dim(inputs_m, m_c, axis=0, keepdims=False)
        tgt = lax.dynamic_index_in_dim(targets_m, m_c, axis=0, keepdims=False)
        # stage 0 sources from the embedding; later stages from the left
        # neighbor's activations. where() zeroes the embedding gradient on
        # stages > 0, so the psum'd embedding grad is exactly stage 0's.
        emb_x = pad_d(embed_lookup(embedding, tok).astype(jnp.float32))
        src = jnp.where(s == 0, emb_x, x_in)
        rng_t = (
            jax.random.fold_in(dropout_rng, m_c * S + s) if use_dropout
            else jnp.zeros((2,), jnp.uint32)
        )
        if uniform:
            # lockstep ticks: compute unconditionally, mask bubble results —
            # auto-axis (TP) collectives inside the stage must not sit under
            # divergent control flow
            ys = jnp.where(active, run_stage(src, rng_t), 0.0)
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(active, is_last), mb_loss(ys, tgt), 0.0
            )
        else:
            ys = lax.cond(
                active,
                run_stage,
                lambda x, r: jnp.zeros((b, T, Dmax), jnp.float32),
                src, rng_t,
            )
            loss_acc = loss_acc + lax.cond(
                jnp.logical_and(active, is_last),
                mb_loss,
                lambda ys, tgt: jnp.zeros((), jnp.float32),
                ys, tgt,
            )
        if S > 1:
            x_in = lax.ppermute(ys, pipe_axis, right)

    loss = lax.psum(loss_acc, pipe_axis) / M  # only the last stage contributed
    return lax.pmean(loss, data_axis)


def make_pp_lm_eval_step(
    cfg: LMConfig,
    mesh: Mesh,
    params_stacked,
    *,
    microbatches: int | None = None,
    tp: bool = False,
):
    """Forward-only eval on the STAGE-SHARDED params (VERDICT r1 weak #7):
    the wavefront runs exactly as in training, deterministic; no host
    gather — the point of PP is that one device cannot hold the model.
    Reports the global token count for exact token-weighted evaluate()."""
    S = mesh.shape["pipe"]
    if microbatches is None:
        microbatches = max(S, 1)
    use_pallas = cfg.use_pallas and not tp
    loss_shard = shard_map(
        lambda p, bt: pp_lm_loss(
            p, bt, cfg, microbatches=microbatches, uniform=tp,
            use_pallas=use_pallas,
        ),
        mesh=mesh,
        in_specs=(pp_lm_param_specs(params_stacked),
                  {"inputs": P("data"), "targets": P("data")}),
        out_specs=P(),
        # Mosaic refuses a pallas_call inside a PARTIALLY-manual shard_map;
        # with the fused kernel live (no TP ⇒ "model"/"seq" are size 1) make
        # every mesh axis manual — semantically identical, Mosaic-legal.
        axis_names=(set(mesh.axis_names) if use_pallas else {"pipe", "data"}),
        check_vma=False,
    )

    def eval_step(params, batch):
        loss = loss_shard(params, batch)
        # jit-level shapes are global, so this is the global token count
        tokens = jnp.asarray(batch["targets"].size, jnp.float32)
        return {"loss": loss, "tokens": tokens}

    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pp_lm_param_shardings(params_stacked, tp=tp),
        is_leaf=lambda x: isinstance(x, P),
    )
    batch_shardings = {
        "inputs": NamedSharding(mesh, P("data")),
        "targets": NamedSharding(mesh, P("data")),
    }
    return jax.jit(eval_step, in_shardings=(param_shardings, batch_shardings))


def make_pp_lm_train_step(
    cfg: LMConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    params_stacked,
    *,
    microbatches: int | None = None,
    donate: bool = True,
    tp: bool = False,
    zero1: bool = False,
):
    """Build the DP x PP (x TP with ``tp=True``) train step on stacked params.

    Batch: {"inputs","targets"} [B, T], B % (data axis * microbatches) == 0.
    ``microbatches`` defaults to the pipe size (pipeline full at steady
    state). Grad/update happen at the jit level: shard_map's transpose
    produces correct grads (psum'd for replicated embedding/head, local for
    the stage-sharded layers), and jit propagates P("pipe") to opt state.

    ``zero1`` composes ZeRO-1 with the stage sharding (VERDICT r3 item 6):
    the optimizer-state moment leaves get their param's spec EXTENDED with
    the "data" axis on an unsharded divisible dimension
    (`zero.zero1_tp_opt_specs` — the same GSPMD weight-update-sharding
    spec tree the TP task runners use, applied to the STACKED
    stage-sharded specs), and the step's in/out shardings PIN them there.
    Each chip then stores 1/(pipe*data) of the moments — the
    memory-relevant pairing for stacked-LSTM scale (config 5). Leaves
    keep full logical shapes, so checkpoints reshard across any later
    dp x pp like plain PP state.

    TP composition is hybrid manual/auto (the train_step.py pattern): the
    shard_map is MANUAL over {"pipe", "data"} only; "model" stays an AUTO
    axis, so GSPMD shards the gate/hidden dims from the jit-level param
    annotations and derives the TP collectives inside each stage's scan.
    Inter-layer dropout (cfg.dropout > 0) uses per-(shard, microbatch,
    layer) folded keys — see pp_lm_loss.
    """
    S = mesh.shape["pipe"]
    L = params_stacked["layers"].W_i.shape[0]
    if L % S != 0:
        raise ValueError(f"{L} layers not divisible by {S} pipeline stages")
    if tp and mesh.shape["model"] > 1 and cfg.hidden_size % mesh.shape["model"]:
        raise ValueError(
            f"hidden {cfg.hidden_size} not divisible by model axis "
            f"{mesh.shape['model']}"
        )
    if microbatches is None:
        microbatches = max(S, 1)

    param_specs = pp_lm_param_specs(params_stacked)
    batch_spec = {"inputs": P("data"), "targets": P("data")}
    # the auto "model" axis cannot partition a pallas_call, so the fused
    # stage-interior kernel is PP-only (no TP hybrid)
    use_pallas = cfg.use_pallas and not tp
    loss_shard = shard_map(
        lambda p, bt, rng: pp_lm_loss(
            p, bt, cfg, microbatches=microbatches, dropout_rng=rng,
            uniform=tp,  # TP collectives need lockstep ticks
            use_pallas=use_pallas,
        ),
        mesh=mesh,
        in_specs=(param_specs, batch_spec, P()),
        out_specs=P(),
        # "model" stays auto (GSPMD TP) — except with the fused kernel live,
        # where Mosaic requires a FULLY-manual shard_map; no TP ⇒ the extra
        # axes are size 1, so making them manual changes nothing semantically
        axis_names=(set(mesh.axis_names) if use_pallas else {"pipe", "data"}),
        check_vma=False,
    )

    def loss_fn(params, batch, rng):
        loss = loss_shard(params, batch, rng)
        return loss, {"loss": loss}

    def step(state: TrainState, batch):
        return step_body(loss_fn, optimizer, state, batch)

    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pp_lm_param_shardings(params_stacked, tp=tp),
        is_leaf=lambda x: isinstance(x, P),
    )
    if zero1:
        opt_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            pp_zero1_opt_specs(optimizer, params_stacked, mesh, tp=tp),
            is_leaf=lambda x: isinstance(x, P),
        )
    else:
        opt_shardings = None  # propagated from params by XLA
    state_shardings = TrainState(
        step=NamedSharding(mesh, P()),
        params=param_shardings,
        opt_state=opt_shardings,
        rng=NamedSharding(mesh, P()),
        carries=None,
    )
    batch_shardings = {
        "inputs": NamedSharding(mesh, P("data")),
        "targets": NamedSharding(mesh, P("data")),
    }

    return jax.jit(
        step,
        in_shardings=(state_shardings, batch_shardings),
        # pin the output state to the input shardings so steps CHAIN: with
        # an auto "model" axis GSPMD may otherwise pick a different layout
        # for e.g. the updated embedding, and the next call's in_shardings
        # pin would reject the committed array
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate else (),
    )
