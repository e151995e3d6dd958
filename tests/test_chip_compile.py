"""Every `pallas_call` of the main paths, compiled by the TPU's own
compiler for a DESCRIBED v5e (no chip attached): interpret mode accepts
kernels Mosaic refuses (a 1-D mask reshaped into a column, an unaligned
slice, too much VMEM), so these compiles are what stands between a
kernel edit and a boot failure on the chip. A compile that passes here
is NOT a chip run — it says nothing about results or times.

Unmarked cases: the kernels alone at published widths (seconds each).
``slow`` cases: the whole jitted config-5 train step and the serve
prefill / decode-step / decode-window programs at 4x1024, V=50,000 —
up to a minute and a half each, run by hand before a chip call:

    JAX_PLATFORMS=cpu python -m pytest tests/test_chip_compile.py -m slow
"""

import contextlib
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from lstm_tensorspark_tpu.models.generate import fuse_layers
from lstm_tensorspark_tpu.models.lstm_lm import LMConfig, init_lm
from lstm_tensorspark_tpu.ops import pallas_decode
from lstm_tensorspark_tpu.ops.lstm_cell import init_lstm_params
from lstm_tensorspark_tpu.ops.pallas_bilstm import pallas_bilstm_scan
from lstm_tensorspark_tpu.ops.pallas_lstm import pallas_lstm_scan

HBM_BYTES = 16 * 10**9  # one v5e chip


def _v5e_devices():
    """The four devices of a described v5e 2x2 host (none is attached)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # no libtpu, or it cannot describe this chip
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")


@pytest.fixture(scope="module")
def chip():
    """Sharding on one device of the described host."""
    return SingleDeviceSharding(_v5e_devices()[0])


@contextlib.contextmanager
def _kernels_selectable():
    """Steer the dispatch onto the kernels: `pallas_lstm.supported()` and
    `pallas_xent.plan()` ask `jax.default_backend()`, which is the CPU
    here — in the test, not through an option of the program."""
    import lstm_tensorspark_tpu.ops.pallas_lstm as pallas_lstm
    import lstm_tensorspark_tpu.ops.pallas_xent as pallas_xent

    real, real_plan = pallas_lstm.supported, pallas_xent.plan
    pallas_lstm.supported = lambda *a, **k: real(*a, **{**k, "platform": "tpu"})
    pallas_xent.plan = lambda *a, **k: real_plan(*a, **{**k, "platform": "tpu"})
    try:
        yield
    finally:
        pallas_lstm.supported, pallas_xent.plan = real, real_plan


@pytest.fixture(autouse=True)
def _as_the_program_compiles():
    """Two process-wide settings differ between the tests and the
    program. tests/conftest.py forces matmul precision "highest" (CPU
    parity tests); Mosaic refuses an fp32-precision contraction of bf16
    operands, and the program never asks for one — compile at JAX's
    default. And a described-device executable is written to the
    persistent cache but cannot be read back without a chip (it warns
    and recompiles): keep the cache off around these compiles."""
    cache_on = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_on)
    jax.config.update("jax_default_matmul_precision", precision)
    compilation_cache.reset_cache()


def _on(chip, tree):
    """Shapes of ``tree`` (arrays or ShapeDtypeStructs) placed on the
    described device — there is no device to hold an array."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _copies_of(compiled, *dims: str) -> list:
    """The `copy` instructions of the compiled text whose result is an f32
    array of one of ``dims`` ("1024,1024")."""
    return re.findall(r"= f32\[(?:%s)\]\S* copy\(" % "|".join(dims),
                      compiled.as_text())


# ---- train recurrence, forward + backward -----------------------------

# (name, B, T, D, H, masked, reversed) — the BASELINE.md configs' scans at
# published widths, bf16 matmuls as the launch scripts run them
# (tests/test_pallas.py pins the backward plan at the same shapes)
TRAIN_SHAPES = [
    ("ptb_char", 64, 64, 128, 128, False, False),
    ("imdb_bilstm_fwd", 64, 400, 256, 256, True, False),
    ("imdb_bilstm_rev", 64, 400, 256, 256, True, True),
    ("wikitext2", 64, 35, 650, 650, False, False),
    ("uci_seq2seq_enc", 64, 168, 370, 256, False, False),
    ("uci_seq2seq_masked_rev", 64, 168, 256, 256, True, True),
    ("wikitext103", 32, 64, 1024, 1024, False, False),
]


def _scan_args(chip, B, T, D, masked):
    xs = jax.ShapeDtypeStruct((B, T, D), jnp.float32)
    mask = jax.ShapeDtypeStruct((B, T), jnp.bool_) if masked else None
    return _on(chip, (xs, mask))


@pytest.mark.parametrize("name,B,T,D,H,masked,reverse", TRAIN_SHAPES,
                         ids=[s[0] for s in TRAIN_SHAPES])
def test_train_recurrence_fwd_bwd_compiles(chip, name, B, T, D, H, masked,
                                           reverse):
    params = jax.eval_shape(
        lambda: init_lstm_params(jax.random.PRNGKey(0), D, H))

    def loss(params, xs, mask):
        (hT, _), ys = pallas_lstm_scan(
            params, xs, mask=mask, reverse=reverse,
            compute_dtype=jnp.bfloat16)
        return jnp.sum(ys) + jnp.sum(hT)

    xs, mask = _scan_args(chip, B, T, D, masked)
    compiled = _compile(jax.grad(loss), _on(chip, params), xs, mask)
    # forward kernel + fused backward kernel (the recompute backward
    # would leave one)
    assert _kernel_calls(compiled) >= 2, name


def test_stacked_bilstm_fwd_bwd_compiles(chip):
    B, T, D, H = 64, 400, 256, 256  # config 2, one bi-LSTM layer
    pf, pb = (jax.eval_shape(
        lambda: init_lstm_params(jax.random.PRNGKey(0), D, H))
        for _ in range(2))

    def loss(pf, pb, xs, mask):
        ((hf, _), ys_f), ((hb, _), ys_b) = pallas_bilstm_scan(
            pf, pb, xs, mask=mask, compute_dtype=jnp.bfloat16)
        return jnp.sum(ys_f) + jnp.sum(ys_b) + jnp.sum(hf) + jnp.sum(hb)

    xs, mask = _scan_args(chip, B, T, D, True)
    compiled = _compile(jax.grad(loss, argnums=(0, 1)),
                        _on(chip, pf), _on(chip, pb), xs, mask)
    assert _kernel_calls(compiled) >= 2


def test_layer_state_keeps_its_layout_under_adam(chip):
    """One config-5 layer (B=64, T=128, H=1024, bf16) trained for two Adam
    steps inside a `lax.scan` whose carry is the donated parameters and
    moments, as the cell's K-step program carries them: the backward
    kernel reads U as it is stored, so the compiler has no reason to carry
    the state transposed and relay it around every Adam fusion."""
    import optax

    B, T, H = 64, 128, 1024
    optimizer = optax.adam(1e-3)
    params = jax.eval_shape(
        lambda: init_lstm_params(jax.random.PRNGKey(0), H, H))
    opt_state = jax.eval_shape(optimizer.init, params)

    def loss(params, xs):
        (hT, _), ys = pallas_lstm_scan(params, xs,
                                       compute_dtype=jnp.bfloat16)
        return jnp.sum(ys) + jnp.sum(hT)

    def two_steps(params, opt_state, xs):
        def step(carry, x):
            params, opt_state = carry
            updates, opt_state = optimizer.update(
                jax.grad(loss)(params, x), opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), None

        return jax.lax.scan(step, (params, opt_state), xs)[0]

    xs = jax.ShapeDtypeStruct((2, B, T, H), jnp.float32)
    compiled = jax.jit(two_steps, donate_argnums=(0, 1)).lower(
        *_on(chip, (params, opt_state, xs))).compile()
    assert _kernel_calls(compiled) >= 2
    assert not _copies_of(compiled, "1024,1024")


# ---- train head + loss ------------------------------------------------

# `%jvp_lm_head_fwd_.1 = (bf16[8192,50000]{1,0:T(8,128)(2,1)}, ...) custom-call(`
_HEAD_KERNEL = re.compile(r"^\s*%[\w.\-]*(lm_head_fwd|lm_head_dx)[\w.\-]* = .* "
                          r"custom-call\(", re.M)
# `%fusion.563 = f32[8192]{0} fusion(%get-tuple-element.71), kind=kLoop, ...`
_FUSION = re.compile(r"^\s*%([\w.\-]+) = (\S+) fusion\(([^)]*)\)", re.M)
_SHAPED = re.compile(r"^\s*%([\w.\-]+) = ([a-z]+\d+)\[([\d,]*)\]", re.M)


def _head_kernels(text: str) -> list[str]:
    return sorted(_HEAD_KERNEL.findall(text))


def _vocab_arrays(text: str, vocab: int, min_bytes: int = 100 * 10**6):
    """Names of the instructions whose result is an array with a ``vocab``
    dimension of ``min_bytes`` or more (the logits at config 5)."""
    big = set()
    for name, dtype, dims in _SHAPED.findall(text):
        shape = [int(d) for d in dims.split(",") if d]
        size = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4}.get(dtype, 4)
        if vocab in shape and math.prod(shape) * size >= min_bytes:
            big.add(name)
    return big


def _scheduled(text: str) -> str:
    """The compiled text without the bodies of fused computations: the
    instructions the device runs one by one."""
    fused = set(re.findall(r"fusion\(.*?calls=%([\w.\-]+)", text))
    kept, skip = [], False
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            skip = head[1] in fused
        if not skip:
            kept.append(line)
        if line.startswith("}"):
            skip = False
    return "\n".join(kept)


def _logits_readers(text: str, rows: int, vocab: int) -> list[str]:
    """XLA fusions whose one operand of the logits' shape ``[rows, vocab]``
    is their only large one (a pass that only reads the logits). The head's
    weight gradient is such a fusion by design (its result is the
    ``[H, V]`` gradient); the rest are what the kernels exist to take out."""
    text = _scheduled(text)
    logits = {name for name, _, dims in _SHAPED.findall(text)
              if dims == f"{rows},{vocab}"}
    found = []
    for name, result, operands in _FUSION.findall(text):
        read = [o for o in re.findall(r"%([\w.\-]+)", operands) if o in logits]
        if len(read) == 1 and f",{vocab}]" not in result.split("{")[0]:
            found.append(f"{name} = {result}")
    return found


def _assert_head_on_kernels(compiled, rows: int, vocab: int = 50_000):
    """The compiled config-5 step: one `lm_head_fwd` and one `lm_head_dx`
    a step, no relayout of a vocabulary array of 100 MB or more, and no
    XLA fusion that only reads the ``[rows, vocab]`` logits (the weight
    gradient's aside)."""
    text = compiled.as_text()
    assert _head_kernels(text) == ["lm_head_dx", "lm_head_fwd"], \
        _head_kernels(text)
    big = _vocab_arrays(text, vocab)
    relayouts = [line.strip()[:160] for line in text.splitlines()
                 if re.match(r"^\s*%([\w.\-]+) = \S+ (copy|transpose)\(", line)
                 and re.match(r"^\s*%([\w.\-]+)", line)[1] in big]
    assert not relayouts, relayouts
    readers = _logits_readers(text, rows, vocab)
    assert not readers, readers


@pytest.mark.parametrize("h,v", [(1024, 50_000), (1024, 32_000),
                                 (2048, 50_000), (128, 300), (4096, 32_000),
                                 (1024, 50_048)])
def test_stored_vocab_major_is_the_compilers_layout(chip, h, v):
    """`pallas_xent.stored_vocab_major` against the layout the chip's
    compiler gives an ``[H, V]`` float32 argument: the kernels engage only
    where their ``[V, H]`` view of the stored head is free."""
    from lstm_tensorspark_tpu.ops import pallas_xent

    text = _compile(lambda w: w * 2, *_on(chip, (
        jax.ShapeDtypeStruct((h, v), jnp.float32),))).as_text()
    layout = re.search(r"entry_computation_layout=\{\(f32\[[\d,]+\]\{([\d,]+)",
                       text)[1]
    assert pallas_xent.stored_vocab_major(h, v) == (layout == "0,1"), layout


@pytest.mark.parametrize("head_dtype,n,h,v", [
    ("float32", 8192, 1024, 50_000), ("bfloat16", 8192, 1024, 50_000),
    ("float32", 2048, 4096, 50_000)])
def test_lm_head_kernels_compile(chip, head_dtype, n, h, v):
    """`ops/pallas_xent.py`'s two kernels at config 5's shape (8,192 rows,
    H=1024, V=50,000: a ragged last V tile), the head as the one-chip step
    reads it (float32) and as a bf16 parameter, through `dense_xent_mean`'s
    value and gradient; and at a width of 4,096, where config 5's tiles
    overflow VMEM and the plan cuts them."""
    from lstm_tensorspark_tpu.ops import xent

    assert xent.pallas_xent.plan(n, h, v, jnp.bfloat16, platform="tpu")

    def loss(ys, head, bias, targets):
        return xent.dense_xent_mean(ys, head, bias, targets, jnp.bfloat16)

    args = _on(chip, (jax.ShapeDtypeStruct((64, n // 64, h), jnp.float32),
                      jax.ShapeDtypeStruct((h, v), jnp.dtype(head_dtype)),
                      jax.ShapeDtypeStruct((v,), jnp.float32),
                      jax.ShapeDtypeStruct((64, n // 64), jnp.int32)))
    with _kernels_selectable():
        compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                            *args)
    _assert_head_on_kernels(compiled, n, v)


@pytest.mark.parametrize("axis", ["seq", "pipe"])
def test_partly_manual_lm_steps_keep_the_xla_head(axis):
    """The sequence- and pipeline-parallel LM steps without `use_pallas`
    make only their own axes manual ({data, seq} or {pipe, data}) and leave
    the rest automatic, "model" among them, of one device here. Mosaic
    lowers no `pallas_call` under such a `shard_map`, whatever the sizes of
    its automatic axes, so the head keeps XLA's operations and the step
    compiles for the four described chips (data 2 x seq or pipe 2) at a
    width and vocabulary the kernels take on one chip."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lstm_tensorspark_tpu.parallel import (
        make_mesh, make_pp_lm_train_step, make_sharded_lm_train_step,
        stack_lm_params)
    from lstm_tensorspark_tpu.train import make_optimizer
    from lstm_tensorspark_tpu.train.loop import init_train_state

    mesh = make_mesh(dp=2, **{"sp" if axis == "seq" else "pp": 2},
                     devices=np.asarray(_v5e_devices()))
    cfg = LMConfig(vocab_size=300, hidden_size=128, num_layers=2,
                   compute_dtype="bfloat16", logits_dtype="bfloat16")
    optimizer = make_optimizer("adam", 1e-3)
    params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    if axis == "seq":
        step = make_sharded_lm_train_step(cfg, optimizer, mesh, params)
    else:
        params = jax.eval_shape(stack_lm_params, params)
        step = make_pp_lm_train_step(cfg, optimizer, mesh, params,
                                     microbatches=2)
    state = jax.eval_shape(lambda: init_train_state(
        params, optimizer, jax.random.PRNGKey(1)))
    rows = P("data", "seq") if axis == "seq" else P("data")
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                     sharding=NamedSharding(mesh, rows))
             for k in ("inputs", "targets")}
    # the leaves the step leaves to propagation, replicated
    state = state._replace(opt_state=jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, P())),
        state.opt_state))
    with _kernels_selectable():
        text = step.lower(state, batch).compile().as_text()
    assert _head_kernels(text) == []


# ---- serve decode windows ---------------------------------------------


def _lm_shapes(chip, cfg):
    return _on(chip, jax.eval_shape(
        lambda: init_lm(jax.random.PRNGKey(0), cfg)))


def _row_args(chip, L, B, H):
    carry = jax.ShapeDtypeStruct((L, B, H), jnp.float32)
    row = jax.ShapeDtypeStruct((B,), jnp.int32)
    alive = jax.ShapeDtypeStruct((B,), jnp.bool_)
    return carry, row, alive


# the default `cli serve` widths (where `auto` picks this kernel on a
# TPU) and a larger plan that still fits the kernel's VMEM budget
DECODE_SHAPES = [(89, 128, 2, 8, 8), (1024, 256, 2, 16, 8)]


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
@pytest.mark.parametrize("V,H,L,B,K", DECODE_SHAPES,
                         ids=["default_widths", "v1024_h256"])
def test_decode_window_compiles(chip, V, H, L, B, K, greedy):
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=L)
    assert pallas_decode.plan_fits(B, K, L, H, cfg.embed, V,
                                   sampled=not greedy)
    carry, row, alive = _row_args(chip, L, B, H)
    noise = (None if greedy
             else jax.ShapeDtypeStruct((K, B, V), jnp.float32))

    def window(params, h, c, tok, alive, rem, eos, noise):
        return pallas_decode.decode_window_call(
            params, fuse_layers(params, cfg), cfg, h, c, tok, alive, rem,
            eos, noise, window=K, temperature=0.8, greedy=greedy,
            interpret=False)

    compiled = _compile(window, _lm_shapes(chip, cfg), *_on(
        chip, (carry, carry, row, alive, row, row, noise)))
    assert _kernel_calls(compiled) == 1


def test_spec_verify_window_compiles(chip):
    V, B, k_draft = 89, 8, 4
    cfg = LMConfig(vocab_size=V, hidden_size=128, num_layers=2)
    dcfg = LMConfig(vocab_size=V, hidden_size=64, num_layers=1)
    assert pallas_decode.spec_plan_fits(
        B, k_draft, cfg.num_layers, cfg.hidden_size, cfg.embed, V,
        dcfg.num_layers, dcfg.hidden_size, dcfg.embed)
    carry, row, alive = _row_args(chip, cfg.num_layers, B, cfg.hidden_size)
    dcarry, _, _ = _row_args(chip, dcfg.num_layers, B, dcfg.hidden_size)

    def window(params, dparams, h, c, dh, dc, tok, alive, rem, eos):
        return pallas_decode.spec_window_call(
            params, fuse_layers(params, cfg), cfg,
            dparams, fuse_layers(dparams, dcfg), dcfg,
            h, c, dh, dc, tok, alive, rem, eos, k_draft=k_draft,
            interpret=False)

    compiled = _compile(
        window, _lm_shapes(chip, cfg), _lm_shapes(chip, dcfg), *_on(
            chip, (carry, carry, dcarry, dcarry, row, alive, row, row)))
    assert _kernel_calls(compiled) == 1


# ---- whole programs at config-5 widths (slow: run before a chip call) --

CONFIG5 = dict(vocab_size=50_000, hidden_size=1024, num_layers=4,
               compute_dtype="bfloat16")


def _fits_hbm(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, m
    return total


@pytest.mark.slow
def test_config5_train_step_compiles(chip):
    """`make_train_step` at the chip_smoke train shape (B=32, T=64,
    --use-pallas, no remat): both fused kernels of every layer present,
    and the program fits one chip's HBM."""
    from lstm_tensorspark_tpu.models import lm_loss
    from lstm_tensorspark_tpu.train import make_optimizer, make_train_step
    from lstm_tensorspark_tpu.train.loop import init_train_state

    cfg = LMConfig(**CONFIG5, logits_dtype="bfloat16", use_pallas=True)
    optimizer = make_optimizer("sgd", 1.0, clip_norm=0.25)
    state = jax.eval_shape(lambda: init_train_state(
        init_lm(jax.random.PRNGKey(0), cfg), optimizer,
        jax.random.PRNGKey(1)))

    def loss_fn(params, batch, rng):
        return lm_loss(params, batch, cfg)

    step = make_train_step(loss_fn, optimizer)
    batch = {k: jax.ShapeDtypeStruct((32, 64), jnp.int32)
             for k in ("inputs", "targets")}
    with _kernels_selectable():
        compiled = jax.jit(step).lower(
            _on(chip, state), _on(chip, batch)).compile()
    assert _kernel_calls(compiled) >= 2 * cfg.num_layers
    _fits_hbm(compiled)


def _state_updated_in_place(compiled, state):
    """The donated train state is the program's to write into: no copy of
    the embedding, the head kernel or one of their Adam moments (205 MB
    each) anywhere in the compiled text, nor of a layer's per-gate matrix
    or one of its moments (240 a step, 1.92 ms, while the backward kernel
    was handed `fused.recurrent.T`: PERF.md, PR 33), and at least the
    parameters and the optimizer's state aliased to outputs."""
    copies = _copies_of(compiled, "50000,1024", "1024,50000", "1024,1024")
    assert not copies, copies[:4]
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((state.params, state.opt_state)))
    assert held > 1.6e9
    assert compiled.memory_analysis().alias_size_in_bytes >= held


@pytest.mark.slow
def test_config5_cell_train_step_donates_its_state(chip):
    """`c5-train-1chip`'s program (benchmark/configs: Adam, clip 1.0,
    dropout 0.2, stateful, device data, B=64 T=128, K=4 steps a dispatch)
    for the described chip: both kernels of every layer, inside HBM, and
    the state updated in place."""
    from lstm_tensorspark_tpu.data.device_dataset import DeviceLMData
    from lstm_tensorspark_tpu.models import lm_loss
    from lstm_tensorspark_tpu.models.lstm_lm import init_carries
    from lstm_tensorspark_tpu.train import (
        make_device_lm_train_step, make_optimizer)
    from lstm_tensorspark_tpu.train.loop import init_train_state

    B, T, n_windows = 64, 128, 100
    cfg = LMConfig(**CONFIG5, logits_dtype="bfloat16", use_pallas=True,
                   dropout=0.2)
    optimizer = make_optimizer("adam", 1e-3, clip_norm=1.0)

    def loss_fn(params, batch, rng, carries):
        return lm_loss(params, batch, cfg, carries=carries, dropout_rng=rng,
                       deterministic=False)

    state = _on(chip, jax.eval_shape(lambda: init_train_state(
        init_lm(jax.random.PRNGKey(0), cfg), optimizer,
        jax.random.PRNGKey(1), carries=init_carries(cfg, B))))
    stream = jax.ShapeDtypeStruct((B, n_windows * T), jnp.int32)
    arrays = _on(chip, {"streams": stream, "shifted": stream})
    data = DeviceLMData(arrays=arrays, batch_size=B, seq_len=T,
                        n_windows=n_windows)
    step = make_device_lm_train_step(
        loss_fn, optimizer, data, steps_per_call=4, stateful=True)
    w0 = _on(chip, jax.ShapeDtypeStruct((), jnp.int32))
    with _kernels_selectable():
        compiled = step.lower(state, arrays, w0).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2 * cfg.num_layers
    _fits_hbm(compiled)
    _state_updated_in_place(compiled, state)
    _assert_head_on_kernels(compiled, B * T)


@pytest.mark.slow
@pytest.mark.parametrize("program", ["prefill", "decode", "decode_window"])
def test_config5_serve_programs_compile(chip, program):
    """The serve engine's three program families at config-5 widths —
    the scan window, since the fused kernel's VMEM plan cannot hold a
    50,000-row embedding (`auto` resolves to scan there)."""
    from lstm_tensorspark_tpu.serve.engine import GREEDY, ServeEngine

    cfg = LMConfig(**CONFIG5)
    assert not pallas_decode.plan_fits(
        8, 8, cfg.num_layers, cfg.hidden_size, cfg.embed, cfg.vocab_size,
        sampled=False)
    # a tiny real engine only to borrow its program builders; the
    # programs are lowered at config-5 SHAPES for the described device
    small = LMConfig(vocab_size=32, hidden_size=8, num_layers=4)
    engine = ServeEngine(init_lm(jax.random.PRNGKey(0), small), small,
                         num_slots=8, decode_kernel="scan")
    engine.cfg = cfg
    params = _lm_shapes(chip, cfg)
    fused = _on(chip, jax.eval_shape(lambda p: fuse_layers(p, cfg), params))
    B, S = 8, 64
    cache = jax.ShapeDtypeStruct(
        (cfg.num_layers, S + 1, cfg.hidden_size), jnp.float32)
    row = jax.ShapeDtypeStruct((B,), jnp.int32)
    flag = jax.ShapeDtypeStruct((B,), jnp.bool_)
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    if program == "prefill":
        fn = engine._get_prefill_fn(B, 128, GREEDY)
        prompts = jax.ShapeDtypeStruct((B, 128), jnp.int32)
        args = (params, cache, cache, row, row, flag, prompts, row, rng)
    elif program == "decode":
        fn = engine._get_decode_fn(B, GREEDY)
        args = (params, fused, cache, cache, row, row, rng)
    else:
        fn = engine._get_decode_window_fn(B, 8, GREEDY)
        args = (params, fused, cache, cache, row, row, flag, row, row, rng)
    compiled = fn.lower(*_on(chip, args)).compile()
    _fits_hbm(compiled)


@pytest.mark.slow
def test_config5_dp4_train_step_compiles():
    """`chip_smoke.py --multichip`'s DP program, compiled for the four
    described chips before a four-chip call is paid for: the shard_map
    device-data step over a ("data",) mesh of 4, B=32 global (8 rows and
    both fused kernels per chip), the per-device memory inside one chip's
    HBM, and the donated state updated in place.

    The weight update of the two 205 MB matrices is sharded
    (train/sharded_update.py): they and their moments live a quarter a
    chip, a step all-gathers the parameter, reduce-scatters its gradient
    and runs Adam on the quarter; the small leaves keep the all-reduce. A
    `copy` of a matrix around the gather, the scatter or the donated alias
    would eat what the quarter saves: gathering the NEW quarter back into
    a whole donated parameter compiled to one copy in and one out of the
    program (PERF.md, PR 35), and PR 33 found 1.92 ms of relayout copies."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lstm_tensorspark_tpu.data.device_dataset import DeviceLMData
    from lstm_tensorspark_tpu.models import lm_loss
    from lstm_tensorspark_tpu.models.lstm_lm import init_carries
    from lstm_tensorspark_tpu.train import (
        make_device_dp_lm_train_step, make_optimizer)
    from lstm_tensorspark_tpu.train.loop import init_train_state
    from lstm_tensorspark_tpu.train.sharded_update import dp_state_spec

    mesh = Mesh(np.asarray(_v5e_devices()), ("data",))
    B, T, n_windows = 32, 64, 100
    cfg = LMConfig(**CONFIG5, logits_dtype="bfloat16", use_pallas=True)
    optimizer = make_optimizer("adam", 1e-3, clip_norm=1.0)

    def loss_fn(params, batch, rng, carries):
        return lm_loss(params, batch, cfg, carries=carries)

    def put(tree, specs):
        return jax.tree.map(lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
            tree, specs)

    state = jax.eval_shape(lambda: init_train_state(
        init_lm(jax.random.PRNGKey(0), cfg), optimizer,
        jax.random.PRNGKey(1), carries=init_carries(cfg, B)))
    spec = dp_state_spec(state, 4, stateful=True)
    split = [s for s in jax.tree.leaves((spec.params, spec.opt_state))
             if s != P()]
    assert sorted(split, key=str) == [P("data")] * 3 + [P(None, "data")] * 3
    state = put(state, spec)
    stream = jax.ShapeDtypeStruct((B, n_windows * T), jnp.int32)
    arrays = {"streams": stream, "shifted": stream}
    arrays = put(arrays, {k: P("data", None) for k in arrays})
    data = DeviceLMData(arrays=arrays, batch_size=B, seq_len=T,
                        n_windows=n_windows)
    step = make_device_dp_lm_train_step(
        loss_fn, optimizer, data, mesh, steps_per_call=4, stateful=True)
    w0 = put(jax.ShapeDtypeStruct((), jnp.int32), P())
    with _kernels_selectable():
        compiled = step.lower(state, arrays, w0).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 * cfg.num_layers
    assert "all-reduce" in text  # the layers' matrices and the biases

    def results(opcode):
        return set(re.findall(r"= \w+(\[[\d,]+\])\S* %s\(" % opcode, text))

    # the forward reads bf16 casts, so the compiler may gather those
    assert results("reduce-scatter") == {"[256,50000]", "[50000,256]"}
    assert results("all-gather") == {"[1024,50000]", "[50000,1024]"}
    # the gathered head is read by XLA's matmuls (pallas_xent's
    # `_mesh_rule`), which take its bf16 half: no float32 head is gathered
    assert re.search(r"= bf16\[1024,50000\]\S* all-gather\(", text)
    assert not re.search(r"= f32\[1024,50000\]\S* all-gather\(", text)
    assert not {"[1024,50000]", "[50000,1024]"} & results("all-reduce")
    # Adam writes the parameter and both moments in one fusion: on quarters
    fused = re.findall(r"= \((f32\[[\d,]+\])\S*, (f32\[[\d,]+\])\S*, "
                       r"(f32\[[\d,]+\])\S*\) fusion\(", text)
    assert ("f32[256,50000]",) * 3 in fused, fused
    assert ("f32[50000,256]",) * 3 in fused, fused
    whole = {"f32[1024,50000]", "f32[50000,1024]"}
    assert not [f for f in fused if whole & set(f)], fused
    copies = _copies_of(compiled, "50000,1024", "1024,50000", "256,50000",
                        "50000,256", "1024,1024")
    assert not copies, copies[:4]
    _fits_hbm(compiled)
    # the gathered head keeps XLA's operations: its weight gradient is
    # faster after XLA's logits than after the kernels' (PERF.md, PR 38)
    assert _head_kernels(text) == []
    # per chip: the small leaves and their moments whole, a quarter of the
    # two matrices and of theirs
    held = sum(
        x.size * x.dtype.itemsize // (1 if s == P() else 4)
        for x, s in zip(jax.tree.leaves((state.params, state.opt_state)),
                        jax.tree.leaves((spec.params, spec.opt_state))))
    assert 0.70e9 < held < 0.72e9
    assert compiled.memory_analysis().alias_size_in_bytes >= held


# ---- the decoder family: paged attention, grouped experts --------------
# the benchmark's two decoder files at their published widths, bf16:
# deepseek-v2-ep4 (latent attention) and mellum2-12b-l8 (grouped-query
# attention, window and full layers)

DSV2, MELLUM = "deepseek-v2-ep4", "mellum2-12b-l8"


def _decoder_cfg(name=DSV2):
    import json
    import os

    from lstm_tensorspark_tpu.models import decoder

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs", name + ".json")
    with open(path) as f:
        return decoder, decoder.DecoderConfig.from_model(json.load(f))


def _int(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _attention_items(tiles, capacity):
    return {"tile": _int(capacity), "page": _int(capacity),
            "start": _int(capacity), "n": _int(1),
            "qpos": _int(tiles), "klen": _int(tiles)}


@pytest.mark.parametrize("model,name,tq,tiles,per_tile,window", [
    (DSV2, "mla_decode", 1, 32, 96, None),
    (DSV2, "mla_prefill", 16, 128, 96, None),
    (MELLUM, "gqa_decode", 1, 32, 192, None),
    (MELLUM, "gqa_decode", 1, 32, 7, 1024),
    (MELLUM, "gqa_prefill", 16, 128, 192, None),
    (MELLUM, "gqa_prefill", 16, 128, 7, 1024)])
def test_paged_attention_compiles(chip, model, name, tq, tiles, per_tile,
                                  window):
    from lstm_tensorspark_tpu.ops import paged_attention

    _, cfg = _decoder_cfg(model)
    h, width = cfg.num_attention_heads, cfg.latent_width
    q = jax.ShapeDtypeStruct((tiles, tq * h, cfg.reading.k_width),
                             jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((2294, 256, width), jnp.bfloat16)

    def attend(q, pool, items):
        return paged_attention.paged_attention(
            q, pool, items, scale=cfg.softmax_scale, reading=cfg.reading,
            window=window, name=name, interpret=False)

    compiled = _compile(attend, *_on(chip, (q, pool, _attention_items(
        tiles, tiles * per_tile))))
    assert _kernel_calls(compiled) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20  # no pool copy


@pytest.mark.parametrize("model,tokens", [(DSV2, 32), (DSV2, 2048),
                                          (MELLUM, 32), (MELLUM, 2048)])
def test_routed_experts_compile(chip, model, tokens):
    from lstm_tensorspark_tpu.ops import moe

    decoder, cfg = _decoder_cfg(model)
    d, inter = cfg.hidden_size, cfg.moe_intermediate_size
    args = (jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((tokens,), jnp.bool_),
            jax.ShapeDtypeStruct((d, cfg.n_routed_experts), jnp.bfloat16),
            jax.ShapeDtypeStruct((cfg.experts_held, d, 2 * inter), jnp.bfloat16),
            jax.ShapeDtypeStruct((cfg.experts_held, inter, d), jnp.bfloat16))

    def routed(x, live, w_router, w_gate_up, w_down):
        return moe.routed_experts(
            x, live, w_router, w_gate_up, w_down, first=cfg.experts_first,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            renormalise=cfg.norm_topk_prob,
            tm=decoder.moe_tile_rows(tokens), interpret=False)

    compiled = _compile(routed, *_on(chip, args))
    assert _kernel_calls(compiled) == 2        # gate/up and down


def _decoder_program(chip, model, program, batch=32, tokens=2048):
    """A decode window (``batch`` rows x 4 steps) or the final prefill of
    ``tokens`` tokens of ``model`` at its published widths, compiled beside
    the pools its file asks for: ``(compiled, pools)``."""
    import threading
    from collections import defaultdict

    from lstm_tensorspark_tpu.serve.decoder_engine import DecoderEngine

    decoder, cfg = _decoder_cfg(model)
    page = 256
    counts, per_row = ((2293,), 96) if model == DSV2 else ((3584, 853), 192)
    kinds = decoder.cache_kinds(cfg, counts)
    params = jax.eval_shape(lambda: decoder.init_decoder(0, cfg))
    absorbed = jax.eval_shape(lambda p: decoder.absorb(p, cfg), params)
    pools = tuple(jax.ShapeDtypeStruct(
        (kinds[k].num_pages + 1, page, cfg.latent_width), jnp.bfloat16)
        for k in cfg.layer_kinds)
    engine = object.__new__(DecoderEngine)     # the programs, not the arrays
    engine.cfg, engine._interpret, engine._fns = cfg, False, {}
    engine._counts_lock, engine.compile_counts = threading.Lock(), defaultdict(int)
    engine.pages_per_row, engine.kinds = per_row, kinds
    engine.cache = type("Pages", (), {
        "page": page, "scratch_pages": counts,
        "window_cap": lambda self, k: 7})()
    acc, n = _int(2, 3), len(kinds)
    if program == "window":
        b = batch
        fn = engine._window_fn(b, 4)
        args = (params, absorbed, pools, acc, _int(b), _int(b),
                jax.ShapeDtypeStruct((b,), jnp.bool_), _int(b), _int(b),
                _int(n, b, per_row),
                [_attention_items(b, engine._items_capacity(b, k))
                 for k in range(n)])
    else:
        t = tokens
        fn = engine._prefill_fn(t, True)
        args = (params, absorbed, pools, acc, _int(t), _int(t),
                jax.ShapeDtypeStruct((t,), jnp.bool_), _int(n, t), _int(t),
                [_attention_items(t // 16, engine._items_capacity(t // 16, k))
                 for k in range(n)], _int(4))
    return fn.lower(*_on(chip, args)).compile(), pools


def _pools_updated_in_place(compiled, pools):
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(p.size * 2 for p in pools)
    for shape in {p.shape for p in pools}:
        dims = ",".join(str(d) for d in shape)
        assert not re.search(rf"= bf16\[{dims}\]\S* copy\(", compiled.as_text())
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16 * 2 ** 30)


@pytest.mark.parametrize("program,size", [("window", 8), ("prefill", 128)])
def test_kv_decoder_programs_compile(chip, program, size):
    """The K/V decoder's decode window at batch 8 and its smallest final
    prefill, at the published widths beside 6 GiB of pools of two kinds:
    they compile, every pool is aliased in place (no copy of one), and
    arguments plus temporaries stay inside the chip's memory."""
    compiled, pools = _decoder_program(chip, MELLUM, program, batch=size,
                                       tokens=size)
    _pools_updated_in_place(compiled, pools)


@pytest.mark.slow
@pytest.mark.parametrize("model", [DSV2, MELLUM])
@pytest.mark.parametrize("program", ["window", "prefill"])
def test_decoder_serve_programs_fit_the_chip(chip, model, program):
    """The decode window (32 rows x 4 steps) and the widest prefill (2,048
    tokens) at the published widths beside the file's pools: they compile,
    the pools are aliased in place (no copy of one), and arguments plus
    temporaries stay inside the chip's memory."""
    compiled, pools = _decoder_program(chip, model, program)
    _pools_updated_in_place(compiled, pools)
