"""Async checkpointing (train/checkpoint.py async_save): background writes
must produce byte-identical restorable checkpoints, serialize one-in-flight,
keep N, and surface writer errors at the next save()/wait()."""

import json
import os

import jax
import numpy as np
import pytest

from lstm_tensorspark_tpu.models import LMConfig, init_lm, lm_loss
from lstm_tensorspark_tpu.train import make_optimizer, make_train_step
from lstm_tensorspark_tpu.train.checkpoint import Checkpointer
from lstm_tensorspark_tpu.train.loop import init_train_state

V, H, B, T = 13, 16, 8, 12


def _setup():
    cfg = LMConfig(vocab_size=V, hidden_size=H, num_layers=1)

    def loss_fn(p, b, r):
        return lm_loss(p, b, cfg)

    opt = make_optimizer("adam", 1e-2)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    state = init_train_state(params, opt, jax.random.PRNGKey(1))
    rng = np.random.RandomState(0)
    batch = {
        "inputs": rng.randint(0, V, (B, T)).astype(np.int32),
        "targets": rng.randint(0, V, (B, T)).astype(np.int32),
    }
    return loss_fn, opt, state, batch


def test_async_save_restores_identically(tmp_path):
    loss_fn, opt, state, batch = _setup()
    step = make_train_step(loss_fn, opt)
    state, _ = step(state, batch)

    sync_dir, async_dir = str(tmp_path / "s"), str(tmp_path / "a")
    Checkpointer(sync_dir).save(state)
    ca = Checkpointer(async_dir, async_save=True)
    ca.save(state)
    ca.wait()
    # byte-identical files → identical restores
    with open(os.path.join(sync_dir, "step_1.msgpack"), "rb") as f:
        want = f.read()
    with open(os.path.join(async_dir, "step_1.msgpack"), "rb") as f:
        got = f.read()
    assert want == got

    template = init_train_state(
        init_lm(jax.random.PRNGKey(9), LMConfig(vocab_size=V, hidden_size=H,
                                                num_layers=1)),
        opt, jax.random.PRNGKey(10),
    )
    restored = ca.restore_latest(template)
    assert int(restored.step) == 1
    for a, b in zip(jax.tree.leaves(restored.params),
                    jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(jax.device_get(b)))


def test_async_snapshot_is_immune_to_later_updates(tmp_path):
    """The host snapshot happens at save() time: training steps taken while
    the write is in flight must NOT leak into the checkpoint."""
    loss_fn, opt, state, batch = _setup()
    step = make_train_step(loss_fn, opt)
    state, _ = step(state, batch)
    want = jax.device_get(state.params)

    ck = Checkpointer(str(tmp_path), async_save=True)
    ck.save(state)
    for _ in range(3):  # keep training immediately
        state, _ = step(state, batch)
    ck.wait()
    template = init_train_state(
        init_lm(jax.random.PRNGKey(9), LMConfig(vocab_size=V, hidden_size=H,
                                                num_layers=1)),
        opt, jax.random.PRNGKey(10),
    )
    restored = ck.restore_latest(template)
    assert int(restored.step) == 1
    for a, b in zip(jax.tree.leaves(restored.params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_keep_n_and_one_in_flight(tmp_path):
    loss_fn, opt, state, batch = _setup()
    step = make_train_step(loss_fn, opt)
    ck = Checkpointer(str(tmp_path), keep=2, async_save=True)
    for _ in range(4):
        state, _ = step(state, batch)
        ck.save(state)  # each save waits for the previous write
    ck.wait()
    names = sorted(n for n in os.listdir(tmp_path) if n.endswith(".msgpack"))
    assert names == ["step_3.msgpack", "step_4.msgpack"]


def test_async_write_error_surfaces_on_next_save(tmp_path, monkeypatch):
    loss_fn, opt, state, batch = _setup()
    step = make_train_step(loss_fn, opt)
    state, _ = step(state, batch)
    ck = Checkpointer(str(tmp_path), async_save=True)

    def boom(host_state):
        raise OSError("disk full (synthetic)")

    monkeypatch.setattr(ck, "_save_single", boom)
    ck.save(state)
    with pytest.raises(OSError, match="disk full"):
        ck.wait()
    # the error is consumed: the checkpointer stays usable
    monkeypatch.undo()
    state, _ = step(state, batch)
    ck.save(state)
    ck.wait()
    assert ck.has_checkpoint()


def test_cli_async_checkpoint_resume(tmp_path):
    """CLI e2e: --async-checkpoint run, then a --resume run continues from
    the restored step."""
    from lstm_tensorspark_tpu.cli import main

    ckpt = str(tmp_path / "ck")
    jsonl = tmp_path / "m.jsonl"
    argv = [
        "--dataset", "ptb_char", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "8", "--seq-len", "16", "--log-every", "2",
        "--backend", "single", "--checkpoint-dir", ckpt,
        "--checkpoint-every", "2", "--async-checkpoint",
    ]
    assert main(argv + ["--num-steps", "4"]) == 0
    assert main(argv + ["--num-steps", "8", "--resume",
                        "--jsonl", str(jsonl)]) == 0
    records = [json.loads(l) for l in open(jsonl)]
    notes = [r for r in records if "resumed at step" in str(r.get("note", ""))]
    # the LAST checkpoint (step 4) must be the resume point — a stale
    # restore (in-flight final write) would resume at step 2
    assert notes and "resumed at step 4" in notes[0]["note"], records


def test_save_best_and_restore_best(tmp_path):
    loss_fn, opt, state, batch = _setup()
    step = make_train_step(loss_fn, opt)
    state, _ = step(state, batch)
    ck = Checkpointer(str(tmp_path))
    ck.save_best(state, 3.14)
    meta = json.load(open(os.path.join(tmp_path, "best.json")))
    assert meta == {"step": 1, "value": 3.14}
    template = init_train_state(
        init_lm(jax.random.PRNGKey(9), LMConfig(vocab_size=V, hidden_size=H,
                                                num_layers=1)),
        opt, jax.random.PRNGKey(10),
    )
    restored = ck.restore_best(template)
    assert int(restored.step) == 1
    for a, b in zip(jax.tree.leaves(restored.params),
                    jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(jax.device_get(b)))
    # best.msgpack lives OUTSIDE the keep-N rotation
    for _ in range(5):
        state, _ = step(state, batch)
        ck.save(state)
    assert os.path.exists(os.path.join(tmp_path, "best.msgpack"))


def test_cli_keep_best_tracks_best_eval(tmp_path):
    """--keep-best: best.json records the step whose eval metric is the
    minimum of all eval records in the run's own JSONL."""
    from lstm_tensorspark_tpu.cli import main

    ckpt = str(tmp_path / "ck")
    jsonl = tmp_path / "m.jsonl"
    rc = main([
        "--dataset", "ptb_char", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "8", "--seq-len", "16", "--num-steps", "8",
        "--log-every", "2", "--eval-every", "2", "--backend", "single",
        "--checkpoint-dir", ckpt, "--checkpoint-every", "4",
        "--keep-best", "--jsonl", str(jsonl),
    ])
    assert rc == 0
    meta = json.load(open(os.path.join(ckpt, "best.json")))
    records = [json.loads(l) for l in open(jsonl)]
    evals = {r["step"]: r["eval_loss"] for r in records
             if "eval_loss" in r and r.get("note") is None}
    best_step = min(evals, key=evals.get)
    assert meta["step"] == best_step
    np.testing.assert_allclose(meta["value"], evals[best_step], rtol=1e-6)


def test_cli_keep_best_requires_dir_and_cadence():
    import pytest

    from lstm_tensorspark_tpu.cli import main

    with pytest.raises(SystemExit):
        main(["--dataset", "ptb_char", "--num-steps", "2", "--keep-best"])


def test_keep_best_survives_resume(tmp_path):
    """A resumed run whose evals are WORSE than the stored best must not
    overwrite best.msgpack (best-so-far is seeded from the saved best)."""
    from lstm_tensorspark_tpu.cli import main

    ckpt = str(tmp_path / "ck")
    argv = [
        "--dataset", "ptb_char", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "8", "--seq-len", "16", "--log-every", "2",
        "--eval-every", "2", "--backend", "single",
        "--checkpoint-dir", ckpt, "--checkpoint-every", "2", "--keep-best",
    ]
    assert main(argv + ["--num-steps", "4", "--learning-rate", "1.0"]) == 0
    before = json.load(open(os.path.join(ckpt, "best.json")))
    # resume with a divergent learning rate: evals only get worse
    assert main(argv + ["--num-steps", "8", "--resume",
                        "--learning-rate", "50.0"]) == 0
    after = json.load(open(os.path.join(ckpt, "best.json")))
    assert after == before, (before, after)

    ck = Checkpointer(ckpt)
    assert ck.best_meta() == before


def test_cli_resume_best(tmp_path):
    """--resume-best restarts from best.msgpack's step, not the latest."""
    from lstm_tensorspark_tpu.cli import main

    ckpt = str(tmp_path / "ck")
    argv = [
        "--dataset", "ptb_char", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "8", "--seq-len", "16", "--log-every", "2",
        "--eval-every", "2", "--backend", "single",
        "--checkpoint-dir", ckpt, "--checkpoint-every", "2", "--keep-best",
    ]
    # run 1: healthy to step 4, then a divergent continuation to step 8 —
    # best stays at an early step while the LATEST checkpoint is step 8
    assert main(argv + ["--num-steps", "4", "--learning-rate", "1.0"]) == 0
    assert main(argv + ["--num-steps", "8", "--resume",
                        "--learning-rate", "50.0"]) == 0
    best = json.load(open(os.path.join(ckpt, "best.json")))
    assert best["step"] < 8

    jsonl = tmp_path / "m.jsonl"
    rc = main(argv + ["--num-steps", str(best["step"] + 2), "--resume-best",
                      "--learning-rate", "0.1", "--jsonl", str(jsonl)])
    assert rc == 0
    records = [json.loads(l) for l in open(jsonl)]
    note = [r for r in records if "BEST" in str(r.get("note", ""))][0]
    assert f"step {best['step']}" in note["note"]


def test_best_tracking_ignores_nan():
    """A NaN eval must never become (and pin) the best."""
    from lstm_tensorspark_tpu.train.loop import train_loop

    saved = []
    evals = iter([float("nan"), 2.0, 1.5])

    def train_step(state, batch):
        return state, {"loss": 0.0, "grad_norm": 0.0}

    loss_fn, opt, state, batch = _setup()
    train_loop(
        state, train_step, iter([batch] * 3), num_steps=3, log_every=0,
        eval_fn=lambda p: {"eval_loss": next(evals)}, eval_every=1,
        best_fn=lambda s, v: saved.append(v),
    )
    assert saved == [2.0, 1.5]


def test_resume_best_fences_abandoned_lineage(tmp_path):
    """--resume-best deletes the abandoned lineage's newer checkpoints, so
    a later --resume continues the NEW lineage."""
    from lstm_tensorspark_tpu.cli import main

    ckpt = str(tmp_path / "ck")
    argv = [
        "--dataset", "ptb_char", "--hidden-units", "16", "--num-layers", "1",
        "--batch-size", "8", "--seq-len", "16", "--log-every", "2",
        "--eval-every", "2", "--backend", "single",
        "--checkpoint-dir", ckpt, "--checkpoint-every", "2", "--keep-best",
    ]
    assert main(argv + ["--num-steps", "4", "--learning-rate", "1.0"]) == 0
    assert main(argv + ["--num-steps", "8", "--resume",
                        "--learning-rate", "50.0"]) == 0
    best = json.load(open(os.path.join(ckpt, "best.json")))
    assert best["step"] < 8
    # rewind: fine-tune from best for 2 more steps
    assert main(argv + ["--num-steps", str(best["step"] + 2),
                        "--resume-best", "--learning-rate", "0.1"]) == 0
    steps = sorted(int(n.split("_")[1].split(".")[0])
                   for n in os.listdir(ckpt) if n.startswith("step_"))
    assert all(s <= best["step"] + 2 for s in steps), steps
    # a plain --resume now continues the fine-tune lineage, not step 8
    jsonl = tmp_path / "m.jsonl"
    assert main(argv + ["--num-steps", str(best["step"] + 4), "--resume",
                        "--learning-rate", "0.1",
                        "--jsonl", str(jsonl)]) == 0
    records = [json.loads(l) for l in open(jsonl)]
    note = [r for r in records if "resumed at step" in str(r.get("note", ""))]
    assert note and f"step {best['step'] + 2}" in note[0]["note"], note


def test_resume_best_requires_dir_and_best():
    import pytest

    from lstm_tensorspark_tpu.cli import main

    with pytest.raises(SystemExit):  # no --checkpoint-dir
        main(["--dataset", "ptb_char", "--num-steps", "2", "--resume-best"])


def test_resume_best_fails_fast_without_best(tmp_path):
    import pytest

    from lstm_tensorspark_tpu.cli import main

    with pytest.raises(SystemExit):  # dir exists but never had --keep-best
        main(["--dataset", "ptb_char", "--num-steps", "2", "--resume-best",
              "--checkpoint-dir", str(tmp_path)])


def test_best_artifact_kinds_never_shadow(tmp_path):
    """A stale single-process best.msgpack must not shadow a newer
    sharded best, and vice versa: each save deletes the other kind, and
    the crash-window arbitration picks the newer step (code-review r4).

    `_save_best_sharded` degenerates cleanly at process_count()==1 (the
    sync barriers no-op, pid 0 writes everything), standing in for the
    multi-process writer."""
    loss_fn, opt, state, batch = _setup()
    # donate=False: the test keeps every step's state to save it later
    step = make_train_step(loss_fn, opt, donate=False)
    state1, _ = step(state, batch)    # step 1
    state2, _ = step(state1, batch)   # step 2
    state3, _ = step(state2, batch)   # step 3

    ck = Checkpointer(str(tmp_path))
    # 1-process best at step 1, then a "multi-process" best at step 2:
    ck.save_best(state1, 3.0)
    assert os.path.exists(os.path.join(str(tmp_path), "best.msgpack"))
    ck._save_best_sharded(state2, 0.5)
    ck._best_meta_cache = None
    # the old best.msgpack is gone; meta and restore follow the shards
    assert not os.path.exists(os.path.join(str(tmp_path), "best.msgpack"))
    assert ck.best_meta() == {"step": 2, "value": 0.5}
    restored = ck.restore_best(jax.device_get(state2))
    np.testing.assert_array_equal(np.asarray(restored.step), 2)

    # and back: a newer single-process best removes the sharded set
    ck.save_best(state3, 0.25)
    ck._best_meta_cache = None
    assert ck.best_meta() == {"step": 3, "value": 0.25}
    left = [n for n in os.listdir(str(tmp_path))
            if n.startswith("best_") or n == "best.complete"]
    assert left == [], left

    # crash-window arbitration: both kinds on disk at once (a crash
    # between writing one and unlinking the other) -> newer step wins
    with open(os.path.join(str(tmp_path), "best.complete"), "w") as f:
        json.dump({"writers": 1, "step": 1, "value": 9.9}, f)
    ck._best_meta_cache = None
    assert ck._best_artifact()[0] == "single"   # single step 3 > sharded 1
    assert ck.best_meta() == {"step": 3, "value": 0.25}
    with open(os.path.join(str(tmp_path), "best.complete"), "w") as f:
        json.dump({"writers": 1, "step": 7, "value": 0.1}, f)
    ck._best_meta_cache = None
    assert ck._best_artifact()[0] == "sharded"  # sharded step 7 > single 3
    assert ck.best_meta() == {"step": 7, "value": 0.1}
