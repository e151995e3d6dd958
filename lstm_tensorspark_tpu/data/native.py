"""ctypes bindings for the native data-pipeline kernels (native/fastdata.cpp)
with transparent pure-Python fallback.

The .so is built on demand via the checked-in Makefile (g++ is part of the
toolchain) into ``native/build/`` (gitignored), under a name that carries
the hash of what it was built from — so a binary is only ever loaded for
the source it came from, whatever the mtimes say and whatever stray
binary a copied tree brings along. If the build or load fails, one line
says so and every entry point falls back to the numpy/Python
implementation with identical results — the native path is a host-side
throughput optimization, never a correctness dependency.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sys

import numpy as np

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_BUILD_DIR = os.path.join(_NATIVE_DIR, "build")

_lib = None
_load_attempted = False


def _so_path() -> str:
    """``native/build/libfastdata-<hash>.so`` for the CURRENT source and
    build recipe (content hash of fastdata.cpp + Makefile)."""
    h = hashlib.sha256()
    for name in ("fastdata.cpp", "Makefile"):
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    return os.path.join(_BUILD_DIR, f"libfastdata-{h.hexdigest()[:16]}.so")


def _build(so_path: str) -> None:
    """Compile to a private name, publish atomically (concurrent test
    workers may build at once), then drop binaries of other sources."""
    tmp = f"{so_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, "-s", f"OUT={tmp}"],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, so_path)
    finally:
        try:
            os.remove(tmp)
        except FileNotFoundError:
            pass  # published by the replace above, or never written
    for old in glob.glob(os.path.join(_BUILD_DIR, "libfastdata-*.so")):
        if old != so_path:
            try:
                os.remove(old)
            except OSError:
                pass  # another process got there first


def _load():
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("LSTM_TSP_NO_NATIVE") == "1":
        return None
    try:
        so_path = _so_path()
        if not os.path.exists(so_path):
            _build(so_path)
        lib = ctypes.CDLL(so_path)
        lib.encode_bytes.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ]
        lib.count_words.restype = ctypes.c_int64
        lib.count_words.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.encode_words.restype = ctypes.c_int64
        lib.encode_words.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ]
        lib.vocab_build.restype = ctypes.c_void_p
        lib.vocab_build.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.vocab_size.restype = ctypes.c_int64
        lib.vocab_size.argtypes = [ctypes.c_void_p]
        lib.vocab_words_bytes.restype = ctypes.c_int64
        lib.vocab_words_bytes.argtypes = [ctypes.c_void_p]
        lib.vocab_fill.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ]
        lib.vocab_free.argtypes = [ctypes.c_void_p]
        lib.csv_decimal_comma.restype = ctypes.c_int64
        lib.csv_decimal_comma.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ]
        _lib = lib
    except (OSError, subprocess.SubprocessError, AttributeError) as e:
        detail = getattr(e, "stderr", None) or e
        if isinstance(detail, bytes):
            detail = detail.decode(errors="replace")
        print("native: could not build/load fastdata ("
              + " ".join(str(detail).split())[:300]
              + ") — using the Python data path", file=sys.stderr, flush=True)
        _lib = None
    return _lib


def available() -> bool:
    return _load() is not None


def encode_chars(text: str, stoi: dict[str, int], unk_id: int) -> np.ndarray:
    """Char-level encoding. Only ASCII vocabularies take the native path
    (byte-level table); others fall back."""
    lib = _load()
    # The byte table only matches Python-level chars when text is pure ASCII
    # (1 byte == 1 char); multi-byte UTF-8 would change lengths and ids.
    # multi-char stoi entries (<pad>/<unk> specials) never appear in raw text.
    chars = {c: i for c, i in stoi.items() if len(c) == 1}
    if (
        lib is not None
        and text.isascii()
        and all(ord(c) < 128 for c in chars)
    ):
        data = text.encode("ascii")
        table = np.full(256, unk_id, np.int32)
        for ch, idx in chars.items():
            table[ord(ch)] = idx
        out = np.empty(len(data), np.int32)
        lib.encode_bytes(
            data, len(data),
            table.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        return out
    return np.asarray([stoi.get(c, unk_id) for c in text], np.int32)


def _ascii_splittable(text: str) -> bool:
    """True when str.split() and the C tokenizer agree: pure-ASCII text
    (the C side matches Python's ASCII whitespace set exactly)."""
    return text.isascii()


def encode_words(
    text: str, itos: list[str], unk_id: int, id_base: int = 0
) -> np.ndarray:
    """Word-level encoding of a whitespace-tokenized text.

    itos: words in id order STARTING at id_base (specials excluded when
    id_base covers them). Tokens not in itos — including literal special
    strings like "<pad>" appearing in raw text — map to unk_id on BOTH
    paths (reserved ids are never reachable from raw text)."""
    lib = _load()
    if (
        lib is not None
        and _ascii_splittable(text)
        # a NUL inside a vocab token would corrupt the \0-delimited buffer
        and all("\0" not in w for w in itos)
    ):
        data = text.encode("ascii")
        vocab_buf = b"\0".join(w.encode("utf-8") for w in itos) + b"\0"
        n_words = lib.count_words(data, len(data))
        out = np.empty(max(n_words, 1), np.int32)
        written = lib.encode_words(
            data, len(data), vocab_buf, len(vocab_buf), len(itos),
            id_base, unk_id,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), len(out),
        )
        return out[:written]
    lookup = {w: id_base + i for i, w in enumerate(itos)}
    return np.asarray(
        [lookup.get(w, unk_id) for w in text.split()], np.int32
    )


def parse_decimal_comma_csv(body: bytes, take: int) -> np.ndarray | None:
    """Parse the body (header already stripped) of a semicolon-separated
    decimal-comma CSV (UCI LD2011_2014 format) into a [rows, take] float32
    array: per line, skip the timestamp field, convert the next ``take``
    values. Returns None when the native library is unavailable OR when
    the C parser hits a value Python's float() might treat differently
    (caller falls back to the pure loop, which keeps the exact historical
    semantics, including its ValueError on garbage)."""
    lib = _load()
    if lib is None or take <= 0:
        return None
    # capacity bound must count every terminator the kernel honors:
    # '\n', lone '\r', and '\r\n' (which would be double-counted by the
    # two substring counts, hence the subtraction)
    max_rows = (body.count(b"\n") + body.count(b"\r")
                - body.count(b"\r\n") + 1)
    out = np.empty((max_rows, take), np.float32)
    rows = lib.csv_decimal_comma(
        body, len(body), take,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size,
    )
    if rows < 0:
        return None
    return out[:rows]


def most_common_words(text: str, max_size: int | None = None) -> list[str]:
    """Whitespace-tokenized vocabulary in ``Counter.most_common`` order
    (count desc, first-occurrence tie-break) — C++ hash-count+sort for ASCII
    text, Python Counter fallback, identical results."""
    if max_size is not None and max_size <= 0:
        return []  # Counter.most_common(n <= 0) semantics on both paths
    lib = _load()
    # NUL gate: a token containing '\0' would corrupt the \0-joined words
    # buffer returned from C++ (one counted word parsed back as two).
    if lib is not None and _ascii_splittable(text) and "\0" not in text:
        data = text.encode("ascii")
        handle = lib.vocab_build(data, len(data))
        try:
            n = lib.vocab_size(handle)
            nbytes = lib.vocab_words_bytes(handle)
            words_buf = ctypes.create_string_buffer(max(nbytes, 1))
            counts = np.empty(max(n, 1), np.int64)
            lib.vocab_fill(
                handle, words_buf,
                counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            )
            words = words_buf.raw[: max(nbytes - 1, 0)].decode("ascii")
            out = words.split("\0") if words else []
        finally:
            lib.vocab_free(handle)
        return out[:max_size] if max_size is not None else out
    from collections import Counter

    most = Counter(text.split()).most_common(max_size)
    return [w for w, _ in most]
