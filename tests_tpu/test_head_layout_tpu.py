"""REAL-TPU check of the config-5 train step's head (ops/xent.py
``dense_xent_mean``): the compiled step of the benchmark's train cells
(64 x 128, 4x1024, V=50,000, bf16 matmuls and logits, Adam, K=4 steps a
dispatch) holds no ``copy``/``transpose`` of a ``[...,50000]`` array of
100 MB or more inside its loop, and the operations that touch the
vocabulary are printed with their times, so a layout regression is caught
by name (run with ``-s`` to see the table).

What it guards (PERF.md section 6, PR 27): with autodiff's backward the two
transposed head matmuls each asked for dlogits in a layout of their own and
XLA wrote the 819 MB array twice (``copy.804``, 2.5 ms of a 43.8 ms step).
And the step runs the head's two Pallas kernels, one
``lm_head_fwd`` and one ``lm_head_dx`` a step (``ops/pallas_xent.py``),
whose times head the printed table.

And of the same executable, after it ran (PERF.md section 6, PR 31): the
train state is donated into it, so it copies none of the six 205 MB arrays
of the embedding and the head at entry, and it aliases the parameters and
the optimizer's state to its outputs.
"""

import glob
import math
import re
import tempfile

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="requires a real TPU"
)

B, T, K, V, H, L = 64, 128, 4, 50_000, 1024, 4
BIG = 100 * 10**6

# `%copy.804 = bf16[64,128,50000]{2,0,1:T(8,128)(2,1)} copy(%get-tuple-element.5345), ...`
_RELAYOUT = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = (?P<dtype>[a-z]+\d+)\[(?P<dims>[\d,]+)\]\S* "
    r"(?:copy|transpose)\(%(?P<operand>[\w.\-]+)")
_OPCODE = re.compile(r"(?<![\w.-])([a-z][a-z0-9-]*)\(")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1}


def build_step():
    """The cell's step as `cli train --device-data --steps-per-call 4`
    builds it, on random tokens: (jitted step, state, arrays, w0)."""
    from lstm_tensorspark_tpu.data.device_dataset import DeviceLMData
    from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
    from lstm_tensorspark_tpu.models.lstm_lm import init_carries
    from lstm_tensorspark_tpu.train import (
        make_device_lm_train_step, make_optimizer)
    from lstm_tensorspark_tpu.train.loop import init_train_state

    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=L,
                   compute_dtype="bfloat16", logits_dtype="bfloat16",
                   use_pallas=True, dropout=0.2)
    optimizer = make_optimizer("adam", 1e-3, clip_norm=1.0)

    def loss_fn(params, batch, rng, carries):
        return lm_loss(params, batch, cfg, carries=carries, dropout_rng=rng,
                       deterministic=False)

    state = init_train_state(
        init_lm(jax.random.PRNGKey(0), cfg), optimizer,
        jax.random.PRNGKey(1), carries=init_carries(cfg, B))
    n_windows = 16
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (B, n_windows * T + 1), 0, V, jnp.int32)
    arrays = {"streams": tokens[:, :-1], "shifted": tokens[:, 1:]}
    data = DeviceLMData(arrays=arrays, batch_size=B, seq_len=T,
                        n_windows=n_windows)
    step = make_device_lm_train_step(
        loss_fn, optimizer, data, steps_per_call=K, stateful=True)
    return step, state, arrays, jnp.zeros((), jnp.int32)


def vocab_relayouts(hlo_text: str, min_bytes: int = BIG) -> list[str]:
    """``copy``/``transpose`` instructions of a compiled program whose
    result (the operand's dims, permuted at most) is a ``[...,V,...]``
    array of ``min_bytes`` or more. Copies of the program's own arguments
    are left out: they are `test_config5_step_updates_its_state_in_place`'s
    to find."""
    arguments = set(re.findall(r"%([\w.\-]+) = \S+ parameter\(", hlo_text))
    found = []
    for line in hlo_text.splitlines():
        m = _RELAYOUT.match(line)
        if not m or m["operand"] in arguments:
            continue
        dims = [int(d) for d in m["dims"].split(",")]
        if V in dims and math.prod(dims) * _BYTES.get(m["dtype"], 4) >= min_bytes:
            found.append(line.strip()[:240])
    return found


def device_op_ms(run, steps: int) -> dict[str, float]:
    """Trace ``run()`` and return milliseconds per optimizer step of every
    leaf operation of chip 0's ``XLA Ops`` line, by its HLO text."""
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[-1]
        data = jax.profiler.ProfileData.from_file(path)
    out: dict[str, float] = {}
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            opcode = _OPCODE.search(e.name.partition(" = ")[2])
            if opcode and opcode[1] in ("while", "conditional", "call"):
                continue  # containers: their interval is their children's
            out[e.name] = out.get(e.name, 0.0) + e.duration_ns / 1e6 / steps
    return out


@pytest.fixture(scope="module")
def cell():
    """The compiled step and the state it is on: every dispatch donates
    the state it is handed, so the tests thread one through."""
    step, state, arrays, w0 = build_step()
    compiled = step.lower(state, arrays, w0).compile()
    on = {"state": state, "calls": 0}

    def dispatches(n):
        for _ in range(n):
            handed = on["state"]
            on["state"], metrics = compiled(handed, arrays, w0 + on["calls"] * K)
            on["calls"] += 1
        loss = float(metrics["loss"])  # the sync: the work is finished
        return loss, handed

    return compiled, dispatches


def test_config5_step_holds_no_vocab_relayout(cell):
    compiled, dispatches = cell
    text = compiled.as_text()
    relayouts = vocab_relayouts(text)
    kernels = sorted(re.findall(
        r"^\s*%[\w.\-]*(lm_head_fwd|lm_head_dx)[\w.\-]* = .* custom-call\(",
        text, re.M))
    dispatches(2)  # warm
    ops = device_op_ms(lambda: dispatches(3), steps=3 * K)
    total = sum(ops.values())
    head = {k: sum(ms for name, ms in ops.items() if k in name.split(" = ")[0])
            for k in ("lm_head_fwd", "lm_head_dx")}
    print(f"\nconfig-5 step, {B} x {T}, V={V}: {total:.2f} ms of device work "
          f"a step; the head's kernels {head['lm_head_fwd']:.2f} (forward) + "
          f"{head['lm_head_dx']:.2f} (dx) ms; operations that touch the "
          f"vocabulary, >= 0.2 ms a step:")
    for name, ms in sorted(ops.items(), key=lambda kv: -kv[1]):
        if str(V) in name and ms >= 0.2:
            print(f"  {ms:6.2f} ms  {re.sub(r'{[^{}]*}', '', name)[:150]}")
    assert not relayouts, "\n".join(relayouts)
    assert kernels == ["lm_head_dx", "lm_head_fwd"], kernels
    assert all(ms > 0 for ms in head.values()), head


def test_config5_step_updates_its_state_in_place(cell):
    compiled, dispatches = cell
    loss, handed = dispatches(3)
    assert math.isfinite(loss)
    leaves = jax.tree.leaves(handed)
    assert all(x.is_deleted() for x in leaves), sum(
        not x.is_deleted() for x in leaves)
    copies = re.findall(r"= f32\[(?:50000,1024|1024,50000)\]\S* copy\(",
                        compiled.as_text())
    assert not copies, copies
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        (handed.params, handed.opt_state)))
    memory = compiled.memory_analysis()
    print(f"\nconfig-5 step: {memory.alias_size_in_bytes / 1e9:.3f} GB aliased "
          f"of {held / 1e9:.3f} GB of parameters and moments; arguments "
          f"{memory.argument_size_in_bytes / 1e9:.3f}, outputs "
          f"{memory.output_size_in_bytes / 1e9:.3f}, temporaries "
          f"{memory.temp_size_in_bytes / 1e9:.3f} GB")
    assert held > 1.6e9
    assert memory.alias_size_in_bytes >= held
