"""The train program names its device work: every `pallas_call` of the
package has a `name=`, the train step's operations carry the device scopes
of `utils/tracing.SCOPES` in their `op_name`, and `utils/op_scopes.py`
maps a compiled program's instructions to those scopes without compiling
anything (what a `--profile-dir` run writes beside its trace)."""

import ast
import glob
import json
import os

import jax
import numpy as np
import optax
import pytest

from lstm_tensorspark_tpu.data import stage_lm_data
from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.models.lstm_lm import init_carries
from lstm_tensorspark_tpu.parallel import make_mesh
from lstm_tensorspark_tpu.train import (
    make_device_dp_lm_train_step,
    make_device_lm_train_step,
    sharded_update,
)
from lstm_tensorspark_tpu.train.loop import init_train_state
from lstm_tensorspark_tpu.train.sharded_update import place_dp_state
from lstm_tensorspark_tpu.utils import op_scopes
from lstm_tensorspark_tpu.utils.tracing import SCOPES, scope

OPS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "lstm_tensorspark_tpu", "ops")
B, T, K, V, H = 8, 8, 2, 64, 16
CFG = LMConfig(vocab_size=V, hidden_size=H, num_layers=2, dropout=0.1)


def _pallas_calls(path):
    tree = ast.parse(open(path).read(), path)
    return [node for node in ast.walk(tree) if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "pallas_call"]


def test_every_pallas_call_in_ops_is_named():
    calls = {path: _pallas_calls(path)
             for path in sorted(glob.glob(os.path.join(OPS, "*.py")))}
    assert sum(map(len, calls.values())) >= 11    # 5 recurrence, 2 bi, 4 more
    unnamed = [f"{os.path.basename(p)}:{c.lineno}" for p, cs in calls.items()
               for c in cs if "name" not in {k.arg for k in c.keywords}]
    assert not unnamed, f"pallas_call without name=: {unnamed}"
    recurrence = _pallas_calls(os.path.join(OPS, "pallas_lstm.py"))
    assert len(recurrence) == 5
    assert all("lstm_rec_" in ast.unparse(k.value) for c in recurrence
               for k in c.keywords if k.arg == "name")


def test_scope_is_one_of_the_fixed_set():
    assert SCOPES == ("embed", "lstm", "head", "optimizer", "dp_sync")
    with pytest.raises(ValueError, match="unknown device scope"):
        scope("attention")


@pytest.mark.parametrize("op_name, want", [
    ("jit(core)/while/body/optimizer/dp_sync/psum", "dp_sync"),
    ("jit(core)/while/body/optimizer/mul", "optimizer"),
    ("jit(core)/while/body/closed_call/transpose(jvp(head))/dot_general",
     "head"),
    ("jit(core)/while/body/jvp(lstm)/while/body/tanh", "lstm"),
    ("jit(head)/add", None),              # a function's name is no scope
    ("jit(core)/while/body/add", None),
])
def test_scope_of_keeps_the_innermost(op_name, want):
    assert op_scopes.scope_of(op_name) == want


HLO = """HloModule jit_core, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %exp.1 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(core)/jvp(head)/exp"}
  ROOT %mul.2 = f32[4]{0} multiply(%exp.1, %exp.1), metadata={op_name="jit(core)/optimizer/mul"}
}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %tanh.3 = f32[4]{0} tanh(%param_0.1), metadata={op_name="jit(core)/transpose(jvp(lstm))/tanh"}
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0:T(256)} parameter(0), metadata={op_name="p[\\'e\\']"}
  %fusion.5 = f32[4]{0:T(256)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(core)/optimizer/mul"}
  %fusion.6 = f32[4]{0} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.1
  %jvp_lstm_rec_fwd_.7 = (f32[4]{0}) custom-call(%fusion.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(core)/jvp(lstm)/pallas_call"}
  %all-reduce-start.8 = f32[4]{0} all-reduce-start(%fusion.6), to_apply=%add, metadata={op_name="jit(core)/optimizer/dp_sync/psum"}
  ROOT %copy.9 = f32[4]{0} copy(%fusion.6)
}
"""


def test_parse_maps_operations_and_lists_mixed_fusions():
    module, ops, mixed = op_scopes.parse(HLO)
    assert module == "jit_core"
    assert ops == {"fusion.5": "optimizer",         # its own op_name decides
                   "fusion.6": "lstm",              # its operations decide
                   "jvp_lstm_rec_fwd_.7": "lstm",
                   "all-reduce-start.8": "dp_sync"}  # the innermost scope
    # a fused computation's instructions never run as operations
    assert "exp.1" not in ops and "tanh.3" not in ops
    assert mixed == {"fusion.5": ["head", "optimizer"]}


# ---- the train programs ----------------------------------------------------


def _lm_loss(params, batch, rng, carries):
    return lm_loss(params, batch, CFG, carries=carries, dropout_rng=rng,
                   deterministic=False)


def _state(opt):
    params = jax.device_get(init_lm(jax.random.PRNGKey(0), CFG))
    return init_train_state(params, opt, jax.random.PRNGKey(1),
                            carries=init_carries(CFG, B))


def _tokens():
    return np.random.RandomState(0).randint(0, V, B * T * 9 + 1).astype(
        np.int32)


def _single():
    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-3))
    data = stage_lm_data(_tokens(), B, T)
    step = make_device_lm_train_step(_lm_loss, opt, data, steps_per_call=K,
                                     stateful=True)
    return step, jax.device_put(_state(opt)), (data.arrays, np.int32(0))


def _dp():
    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-3))
    mesh = make_mesh(dp=2, devices=np.asarray(jax.devices()[:2]))
    data = stage_lm_data(_tokens(), B, T, mesh=mesh)
    step = make_device_dp_lm_train_step(_lm_loss, opt, data, mesh,
                                        steps_per_call=K, stateful=True)
    state = place_dp_state(_state(opt), mesh, stateful=True)
    return step, state, (data.arrays, np.int32(0))


@pytest.fixture
def large_leaves(monkeypatch):
    """The embedding and the head (16 KiB) live sharded in the DP step, so
    its program gathers and reduce-scatters as config 5's does."""
    monkeypatch.setattr(sharded_update, "MIN_SHARDED_BYTES", 4 * 1024)


@pytest.mark.parametrize("build, want", [
    (_single, {"embed", "lstm", "head", "optimizer"}),
    (_dp, set(SCOPES)),
])
def test_train_step_program_holds_the_scopes(build, want, large_leaves):
    step, state, rest = build()
    module, ops, _ = op_scopes.parse(
        step.lower(state, *rest).compile().as_text())
    assert module == "jit_core"
    assert set(ops.values()) == want


class _Compiles:
    """Backend compiles of this process while open (as the benchmark's
    `observe.Compiles` counts them: cache fetches included)."""

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        return self

    def _seen(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._seen)


@pytest.mark.parametrize("build", [_single, _dp])
def test_map_is_written_without_compiling(build, tmp_path, large_leaves):
    step, state, rest = build()
    step = op_scopes.LastCall(step)
    assert step.lower(state) is None              # nothing dispatched yet
    for w in (0, K):                              # the state is donated
        state, metrics = step(state, rest[0], np.int32(w))
    jax.block_until_ready(metrics["loss"])
    with _Compiles() as compiles:
        written = op_scopes.write(str(tmp_path), step.lower(state))
    assert compiles.n == 0
    with open(tmp_path / op_scopes.FILE) as f:
        assert json.load(f) == written
    assert set(written) == {"jit_core"}
    assert {"embed", "lstm", "head", "optimizer"} <= set(
        written["jit_core"]["ops"].values())


def test_cli_writes_the_map_beside_its_profile(tmp_path):
    from lstm_tensorspark_tpu.cli import main

    common = ["--dataset", "ptb_char", "--hidden-units", "16",
              "--batch-size", "8", "--seq-len", "16", "--num-steps", "4",
              "--log-every", "2", "--backend", "single", "--stateful",
              "--device-data", "--steps-per-call", "2"]
    out = tmp_path / "profile"
    jsonl = tmp_path / "run.jsonl"
    assert main(common + ["--profile-dir", str(out),
                          "--jsonl", str(jsonl)]) == 0
    with open(out / op_scopes.FILE) as f:
        written = json.load(f)
    assert set(written) == {"jit_core"}
    assert set(written["jit_core"]["ops"].values()) == {
        "embed", "lstm", "head", "optimizer"}
    notes = [json.loads(line) for line in open(jsonl)]
    (note,) = [r for r in notes if r.get("note") == "op_scopes"]
    assert note["ops"] == {"jit_core": len(written["jit_core"]["ops"])}
