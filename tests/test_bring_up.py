"""No fallback that hides the device, no state a copied tree smuggles in:
the recurrence path is named (or refused), a wrong --data-path is an
error, the compile cache is placed from outside, and the native kernels
are built from the source that is there."""

import argparse
import os

import jax
import jax.numpy as jnp
import pytest

from lstm_tensorspark_tpu.models.lstm_lm import LMConfig
from lstm_tensorspark_tpu.ops.scan import recurrence_path


# ---- which recurrence runs: named on the CPU, refused on a TPU ---------


@pytest.fixture()
def on_tpu(monkeypatch):
    """Steer the code that asks `jax.default_backend()` — in the test,
    not through an option of the program. Nothing is traced under it:
    `recurrence_path` and `recurrence_note` are pure functions of shapes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _path(batch, **kw):
    return recurrence_path(batch, 64, 1024, 1024, use_pallas=True,
                           compute_dtype=jnp.bfloat16, **kw)


def test_recurrence_path_on_the_cpu_is_the_scan_and_says_why():
    path, note = _path(32)
    assert path == "scan"
    assert "TPU programs and this is cpu" in note and "B=32" in note
    assert recurrence_path(32, 64, 8, 8, use_pallas=False) == (
        "scan", "lax.scan (--use-pallas not given)")


# config 5's widths (H=1024, bf16): what the VMEM plan makes of each batch
@pytest.mark.parametrize("batch,kw,path,says", [
    (32, {}, "pallas", "fwd=resident bwd=resident"),
    (64, {}, "pallas", "bwd=resident"),
    (32, {"remat_chunk": 32}, "pallas",
     "bwd=recompute lax.scan (--remat-chunk)"),
    (128, {}, "pallas", "bwd=recompute lax.scan (no fused backward fits)"),
    (256, {}, "scan", "no kernel strategy fits VMEM"),  # the script's own B
    (30, {}, "scan", "not a multiple of 8"),
])
def test_recurrence_path_at_config5_widths(on_tpu, batch, kw, path, says):
    got, note = _path(batch, **kw)
    assert got == path and says in note, note


def _args(batch_size, **kw):
    return argparse.Namespace(batch_size=batch_size, grad_accum=1, **kw)


def test_use_pallas_no_layer_can_honour_is_an_error_on_a_tpu(on_tpu):
    from lstm_tensorspark_tpu.cli import recurrence_note

    cfg = LMConfig(vocab_size=50_000, hidden_size=1024, num_layers=4,
                   compute_dtype="bfloat16", use_pallas=True)
    d_ins = [cfg.embed] + [cfg.hidden_size] * 3
    with pytest.raises(SystemExit) as e:
        recurrence_note(_args(256), cfg, 1, 128, d_ins)
    assert "B=256" in str(e.value) and "H=1024" in str(e.value)
    # the same flags over four chips plan: the per-device batch is 64
    note = recurrence_note(_args(256), cfg, 4, 128, d_ins)
    assert note.startswith("pallas fwd=") and "B=64" in note


def test_use_pallas_on_the_cpu_keeps_the_scan_with_the_note():
    from lstm_tensorspark_tpu.cli import recurrence_note

    cfg = LMConfig(vocab_size=50, hidden_size=1024, compute_dtype="bfloat16",
                   use_pallas=True)
    note = recurrence_note(_args(256), cfg, 1, 128, [1024])
    assert note.startswith("lax.scan (") and "this is cpu" in note


# ---- --data-path ---------------------------------------------------------


@pytest.mark.parametrize("dataset", ["wikitext103", "imdb",
                                     "uci_electricity"])
def test_data_path_without_the_files_is_an_error(tmp_path, dataset):
    from lstm_tensorspark_tpu.data import get_dataset
    from lstm_tensorspark_tpu.data.datasets import DataPathError

    with pytest.raises(DataPathError, match="not found there"):
        get_dataset(dataset, str(tmp_path))          # exists, holds nothing
    with pytest.raises(DataPathError):
        get_dataset(dataset, str(tmp_path / "typo"))  # does not exist


def test_no_data_path_is_the_stand_in_and_the_note_states_its_vocab(capsys):
    from lstm_tensorspark_tpu.cli import main

    rc = main(["--dataset", "ptb_char", "--hidden-units", "8",
               "--batch-size", "8", "--seq-len", "8", "--num-steps", "1",
               "--backend", "single"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no --data-path, using the synthetic stand-in (vocabulary " in out


# ---- the compile cache is placed from outside ---------------------------


@pytest.fixture()
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("from_outside", [True, False])
def test_compile_cache_placement(monkeypatch, cache_config, tmp_path,
                                 from_outside):
    from lstm_tensorspark_tpu.utils import compile_cache

    jax.config.update("jax_compilation_cache_dir", None)
    if from_outside:
        # JAX reads the variable itself (at import); the program must set
        # NO directory in code on top of it
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        compile_cache.place_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.place_compile_cache() == (
            compile_cache.DEFAULT_CACHE_DIR)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache")
    # either way every executable is cached
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


# ---- native kernels: built from the source that is there ----------------


@pytest.fixture()
def fresh_native():
    from lstm_tensorspark_tpu.data import native

    def reset():
        native._load_attempted = False
        native._lib = None

    reset()
    yield native
    reset()


def test_native_rebuilds_when_the_binary_is_of_another_source(
        fresh_native, monkeypatch, tmp_path):
    native = fresh_native
    build = tmp_path / "build"
    build.mkdir()
    # what a copied tree brings along: a binary named for ANOTHER source
    stray = build / "libfastdata-0123456789abcdef.so"
    stray.write_bytes(b"not even an ELF file")
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    assert native.available()
    current = os.path.basename(native._so_path())
    assert current != stray.name
    assert os.listdir(build) == [current]  # built from source; stray gone
    assert native.encode_chars("abc", {"a": 0, "b": 1, "c": 2}, 9).tolist() \
        == [0, 1, 2]


def test_native_failed_build_says_so_once_and_python_takes_over(
        fresh_native, monkeypatch, tmp_path, capfd):
    native = fresh_native
    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "build"))
    monkeypatch.setenv("CXX", "/nonexistent/compiler")
    assert not native.available()
    assert native.encode_chars("abc", {"a": 0, "b": 1, "c": 2}, 9).tolist() \
        == [0, 1, 2]
    err = capfd.readouterr().err
    assert err.count("native: could not build/load fastdata") == 1
    assert "using the Python data path" in err
