"""Dataset registry for the five baseline configs (BASELINE.md).

Each entry returns real data from ``data_path``; with NO ``data_path`` it
returns a deterministic synthetic stand-in with identical interface —
required by the no-network environment (SURVEY.md §7 "Hard parts"). A
``data_path`` that is given and holds no such files is an error
(`DataPathError`): the stand-ins are smaller than the corpora they stand in
for (a 5,000-word chain for WikiText-103's 50,000-word vocabulary), so a
typo would silently train a different model.

Returned dict: {"train","valid","test"} token arrays (LM) or
(sequences, labels) tuples (classification) or float arrays (forecasting),
plus "vocab" where applicable and "synthetic": bool.
"""

from __future__ import annotations

import os

import numpy as np

from .corpus import (
    build_char_vocab,
    build_word_vocab,
    load_text,
    resolve_split_files,
    synthetic_text,
)


class DataPathError(ValueError):
    """``data_path`` was given and the dataset's files are not there."""


def _no_files(data_path: str, what: str) -> DataPathError:
    return DataPathError(
        f"--data-path {data_path!r}: {what} not found there; omit "
        "--data-path to train on the synthetic stand-in")


# Version tag for the synthetic word-corpus CACHE FORMAT+ALGORITHM. Bump on
# any change to synthetic_word_corpus (or its defaults) so stale caches with
# a matching token count are never reused across generator versions.
_CORPUS_FMT = "v1"


# Stale-cache sweep age gate: entries from OTHER format versions are only
# deleted once untouched this long. A concurrent checkout of a different
# version (cross-version quality race) keeps refreshing its own entries'
# mtimes, so two live versions no longer delete and regenerate each
# other's multi-MB corpora on every leg (ADVICE r5 finding 3); genuinely
# orphaned versions still get cleaned up after the window passes.
_CACHE_STALE_AGE_S = 7 * 24 * 3600


def _sweep_stale_corpus_cache(cache_root: str) -> None:
    """Delete cache entries that belong to other format versions AND have
    not been touched for ``_CACHE_STALE_AGE_S``: version subdirectories
    other than the current ``_CORPUS_FMT`` one, plus legacy flat
    ``words_*`` files from the pre-namespaced layout."""
    import time

    # wall clock on purpose: the cutoff is compared against st_mtime
    # below, which is wall-clock time — monotonic would be wrong here
    cutoff = time.time() - _CACHE_STALE_AGE_S  # graftlint: disable=wallclock-timing
    try:
        entries = os.listdir(cache_root)
    except OSError:
        return
    for name in entries:
        if name == _CORPUS_FMT:
            continue
        p = os.path.join(cache_root, name)
        try:
            if os.path.isdir(p):
                for f in os.listdir(p):
                    fp = os.path.join(p, f)
                    if os.path.getmtime(fp) < cutoff:
                        os.remove(fp)
                if not os.listdir(p):
                    os.rmdir(p)
            elif name.startswith("words_") and os.path.getmtime(p) < cutoff:
                os.remove(p)
        except OSError:
            pass  # sweeping is best-effort housekeeping


def _cached_word_stream(n_tokens: int, vocab_size: int, seed: int,
                        noise: float, generate) -> list:
    """Token list of ``generate(n_tokens, vocab_size, seed=, noise=)``,
    cached as plain text under the system temp dir, keyed by every
    generation parameter, inside a per-``_CORPUS_FMT`` subdirectory (bump
    the tag whenever the generator algorithm changes, or a stale cache
    whose token count still matches silently skews cross-version
    quality-race comparisons — ADVICE r4). Namespacing by version means
    checkouts of different versions each keep their own cache instead of
    sweeping each other's (ADVICE r5 finding 3); other versions' entries
    are only removed once old (`_sweep_stale_corpus_cache`). A
    missing/corrupt/short cache regenerates silently — the cache is an
    optimization, never a correctness dependency (atomic tmp+rename
    write; concurrent legs at worst both generate and one rename wins)."""
    import tempfile

    cache_root = os.path.join(tempfile.gettempdir(), "lstm_tsp_corpus_cache")
    cache_dir = os.path.join(cache_root, _CORPUS_FMT)
    path = os.path.join(
        cache_dir, f"words_{n_tokens}_{vocab_size}_{seed}_{noise}.txt")
    try:
        # no exists() pre-check: another checkout's age-gated sweep can
        # remove the file between the stat and the open (the TOCTOU
        # class) — a missing cache is just the OSError miss below
        with open(path, "r", encoding="ascii") as f:
            stream = f.read().split()
    except OSError:
        stream = None  # no/unreadable cache: regenerate below
    if stream is not None and len(stream) == n_tokens:
        try:
            # a HIT must refresh mtime: reads alone don't, and the
            # age-gated sweep keys liveness off mtime — without this, a
            # daily-used foreign-version cache would still look stale
            # after the window and get swept
            os.utime(path, None)
        except OSError:
            pass
        return stream
    text = generate(n_tokens, vocab_size, seed=seed, noise=noise)
    try:
        os.makedirs(cache_dir, exist_ok=True)
        _sweep_stale_corpus_cache(cache_root)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w", encoding="ascii") as f:
            f.write(text)
        os.replace(tmp, path)
    except OSError:
        pass  # cache write failure is not an error
    return text.split()


def _lm_dataset(
    data_path: str | None,
    basenames: list[str],
    level: str,
    *,
    synthetic_tokens: int,
    max_vocab: int | None = None,
    seed: int = 0,
    synthetic_vocab: int | None = None,
    synthetic_noise: float = 0.05,
):
    files = resolve_split_files(data_path or "", basenames)
    synthetic = files is None
    if synthetic and data_path:
        raise _no_files(
            data_path, "train/valid/test files named "
            f"{basenames[0]}.<split>.txt, <split>.txt or "
            f"{basenames[0]}.<split>.tokens")
    if synthetic:
        if synthetic_vocab is not None:
            # controlled-entropy stand-in (word LMs): the splits share the
            # SAME chain (same seed) — valid/test measure generalization
            # over held-out samples of one process, like real corpora.
            # The stream is cached on disk (keyed by every generation
            # parameter): the 2M-token chain costs ~1.2 s per process
            # launch, a pure fixed cost in the launch-to-quality races
            # that both platforms would otherwise re-pay every leg.
            from .corpus import synthetic_word_corpus

            # one long stream, sliced — cheaper than three generations
            stream = _cached_word_stream(
                int(synthetic_tokens * 1.2), synthetic_vocab, seed,
                synthetic_noise, synthetic_word_corpus,
            )
            n, tenth = synthetic_tokens, synthetic_tokens // 10
            texts = {
                "train": " ".join(stream[:n]),
                "valid": " ".join(stream[n:n + tenth]),
                "test": " ".join(stream[n + tenth:n + 2 * tenth]),
            }
        else:
            texts = {
                "train": synthetic_text(synthetic_tokens, seed),
                "valid": synthetic_text(synthetic_tokens // 10, seed + 1),
                "test": synthetic_text(synthetic_tokens // 10, seed + 2),
            }
    else:
        texts = {s: load_text(p) for s, p in files.items()}

    if level == "char":
        vocab = build_char_vocab(texts["train"])
    else:
        vocab = build_word_vocab(texts["train"], max_vocab)

    out = {s: vocab.encode_text(t, level) for s, t in texts.items()}
    out["vocab"] = vocab
    out["synthetic"] = synthetic
    return out


def ptb_char(data_path=None, **kw):
    """BASELINE.md config 1: Penn Treebank char-level."""
    return _lm_dataset(
        data_path, ["ptb", "ptb.char"], "char", synthetic_tokens=200_000, **kw
    )


def wikitext2_word(data_path=None, **kw):
    """BASELINE.md config 3: WikiText-2 word-level. Synthetic stand-in:
    controlled-entropy 1,000-word chain (synthetic_word_corpus) so the
    eval-ppl curve declines across hundreds of steps — the old
    seed-paragraph chain (~113 words) saturated by step ~20 and quality
    races measured launch costs (VERDICT r3 weak 2)."""
    kw.setdefault("synthetic_vocab", 1_000)
    kw.setdefault("synthetic_noise", 0.05)
    return _lm_dataset(
        data_path, ["wiki", "wikitext-2"], "word",
        synthetic_tokens=400_000, max_vocab=33_278, **kw
    )


def wikitext103_word(data_path=None, **kw):
    """BASELINE.md config 5: WikiText-103 word-level (synthetic stand-in is
    deliberately larger: a controlled-entropy 5,000-word chain — see
    wikitext2_word's note)."""
    kw.setdefault("synthetic_vocab", 5_000)
    kw.setdefault("synthetic_noise", 0.1)
    return _lm_dataset(
        data_path, ["wiki", "wikitext-103"], "word",
        synthetic_tokens=2_000_000, max_vocab=50_000, **kw
    )


def _resolve_imdb_root(data_path: str | None) -> str | None:
    """Locate the standard aclImdb directory layout: ``<root>/{train,test}/
    {pos,neg}/*.txt``. Accepts the aclImdb dir itself or a parent containing
    it; None when absent (synthetic fallback)."""
    if not data_path or not os.path.isdir(data_path):
        return None
    for root in (data_path, os.path.join(data_path, "aclImdb")):
        if all(
            os.path.isdir(os.path.join(root, split, label))
            for split in ("train", "test")
            for label in ("pos", "neg")
        ):
            return root
    return None


def _read_imdb_split(root: str, split: str, max_examples: int | None = None):
    """Read one aclImdb split into (texts, labels), deterministic order."""
    texts, labels = [], []
    for label_name, label in (("pos", 1), ("neg", 0)):
        d = os.path.join(root, split, label_name)
        names = [n for n in sorted(os.listdir(d)) if n.endswith(".txt")]
        if max_examples is not None:
            names = names[: max_examples // 2]
        for name in names:
            with open(os.path.join(d, name), encoding="utf-8",
                      errors="replace") as f:
                texts.append(f.read())
            labels.append(label)
    return texts, labels


def _imdb_real(root: str, *, max_len: int, max_vocab: int = 25_000,
               valid_frac: float = 0.1, max_examples: int | None = None,
               seed: int = 0):
    """aclImdb directory → the same dict interface as the synthetic path:
    word-id sequences clipped to ``max_len``, labels, train-split vocab."""
    train_texts, train_labels = _read_imdb_split(root, "train", max_examples)
    test_texts, test_labels = _read_imdb_split(root, "test", max_examples)
    vocab = build_word_vocab(" ".join(train_texts), max_vocab)

    def encode(texts, labels):
        seqs = [vocab.encode_text(t, "word")[:max_len] for t in texts]
        return seqs, np.asarray(labels, np.int32)

    # interleave pos/neg before the valid split so both splits stay balanced
    order = np.random.RandomState(seed).permutation(len(train_texts))
    train_texts = [train_texts[i] for i in order]
    train_labels = [train_labels[i] for i in order]
    n_valid = int(len(train_texts) * valid_frac)
    seqs, labels = encode(train_texts, train_labels)
    test_seqs, test_labels = encode(test_texts, test_labels)
    return {
        "train": (seqs[n_valid:], labels[n_valid:]),
        "valid": (seqs[:n_valid], labels[:n_valid]),
        "test": (test_seqs, test_labels),
        "vocab": vocab,
        "num_classes": 2,
        "max_len": max_len,
        "synthetic": False,
    }


def imdb(data_path=None, *, num_examples: int | None = None, max_len: int = 400,
         seed: int = 0, signal: float = 0.25):
    """BASELINE.md config 2: binary sentiment over variable-length sequences.

    Real data: point ``data_path`` at the aclImdb directory (or its parent) —
    standard ``{train,test}/{pos,neg}/*.txt`` layout. Synthetic stand-in
    otherwise: two word distributions shifted by class, lengths drawn
    log-uniform in [20, max_len] — learnable by a bi-LSTM, label balance
    exact. ``signal`` is the class-specific token fraction (the SNR knob):
    the old 0.7 made a seq-400 example carry ~hundreds of informative
    tokens, the model saturated accuracy 1.0 by step ~40, and the quality
    race measured launch costs instead of training (VERDICT r3 weak 2);
    0.25 leaves ~5-100 informative tokens per example (length-dependent)
    so the accuracy curve climbs over hundreds of steps.

    ``num_examples`` bounds BOTH paths (per split, balanced); the default
    loads everything real / 2000 synthetic.
    """
    root = _resolve_imdb_root(data_path)
    if root is not None:
        return _imdb_real(root, max_len=max_len, seed=seed,
                          max_examples=num_examples)
    if data_path:
        raise _no_files(data_path, "aclImdb/{train,test}/{pos,neg}/*.txt")
    num_examples = num_examples or 2000
    rng = np.random.RandomState(seed)
    text = synthetic_text(50_000, seed)
    vocab = build_word_vocab(text)
    V = len(vocab)
    pos_words = np.arange(2, V, 2)
    neg_words = np.arange(3, V, 2)
    sequences, labels = [], []
    for i in range(num_examples):
        label = i % 2
        length = int(np.exp(rng.uniform(np.log(20), np.log(max_len))))
        base = pos_words if label else neg_words
        mix = rng.rand(length) < signal  # class-specific vs shared noise
        seq = np.where(
            mix, base[rng.randint(len(base), size=length)],
            rng.randint(2, V, size=length),
        ).astype(np.int32)
        sequences.append(seq)
        labels.append(label)
    labels = np.asarray(labels, np.int32)
    n_train = int(num_examples * 0.8)
    n_valid = int(num_examples * 0.1)
    return {
        "train": (sequences[:n_train], labels[:n_train]),
        "valid": (sequences[n_train : n_train + n_valid], labels[n_train : n_train + n_valid]),
        "test": (sequences[n_train + n_valid :], labels[n_train + n_valid :]),
        "vocab": vocab,
        "num_classes": 2,
        "max_len": max_len,
        "synthetic": True,
    }


def _resolve_uci_file(data_path: str | None) -> str | None:
    """Locate the UCI ElectricityLoadDiagrams file (``LD2011_2014.txt``):
    accepts the file itself or a directory containing it."""
    if not data_path:
        return None
    if os.path.isfile(data_path):
        return data_path
    if os.path.isdir(data_path):
        p = os.path.join(data_path, "LD2011_2014.txt")
        if os.path.isfile(p):
            return p
    return None


def _uci_real(path: str, *, num_series: int):
    """Parse the UCI semicolon-separated CSV: first column is a timestamp,
    remaining columns are per-customer loads with DECIMAL COMMAS (European
    locale — the dataset's documented format). Keeps the first
    ``num_series`` customer columns, per-series normalised, 80/10/10
    time-ordered split — identical interface to the synthetic path.

    The per-value parse is the slowest host step on the real ~700 MB file,
    so it takes the C++ kernel (native/fastdata.cpp csv_decimal_comma)
    when available — byte-identical output (parse-to-double then cast,
    exactly like the Python loop; measured 2.9x end-to-end on a 39 MB
    synthetic file), pure-Python loop otherwise."""
    from .native import available, parse_decimal_comma_csv

    # header via TEXT mode: universal newlines, exactly like the fallback
    # loop below (a binary readline would mis-read CR-only files)
    with open(path, encoding="utf-8", errors="replace") as f:
        ncols = f.readline().count(";")
    take = min(num_series, ncols) if ncols else num_series
    data = None
    if available() and take > 0:
        with open(path, "rb") as fb:
            # locate the end of the header in a small prefix, then seek
            # and read ONLY the body — one copy of the ~700 MB file, for
            # the kernel alone (the fallback path streams line-by-line).
            # The skip stops at the FIRST line terminator of any style,
            # matching the text-mode sniff above (a binary readline would
            # eat the first data row of a \r-header/\n-body mixed file);
            # CR-only bodies then parse 0 rows (the kernel splits on \n)
            # or hit the -2 sentinel, and the text fallback handles them
            # as it always did.
            prefix = fb.read(1 << 20)  # headers are ~KBs; 1 MiB is ample
            i_r, i_n = prefix.find(b"\r"), prefix.find(b"\n")
            ends = [i for i in (i_r, i_n) if i >= 0]
            if ends:
                i = min(ends)
                i += 2 if prefix[i:i + 2] == b"\r\n" else 1
                fb.seek(i)
                body = fb.read()
                data = parse_decimal_comma_csv(body, take)
                del body
    if data is not None and not len(data):
        data = None  # empty parse: let the fallback raise the format error
    if data is None:
        rows = []
        with open(path, encoding="utf-8", errors="replace") as f:
            f.readline()  # header (column count already derived above)
            for line in f:
                parts = line.rstrip("\n").split(";")
                if len(parts) < take + 1:
                    continue
                rows.append(
                    [float(v.replace(",", ".") or 0.0)
                     for v in parts[1 : take + 1]]
                )
        if not rows:
            raise ValueError(
                f"{path} does not look like the UCI LD2011_2014 format "
                "(semicolon-separated, timestamp + per-customer columns)"
            )
        data = np.asarray(rows, np.float32)  # [length, take]
    n_train = int(len(data) * 0.8)
    n_valid = int(len(data) * 0.1)
    # normalise with TRAIN-split statistics only — using full-series stats
    # would leak valid/test information into the scored data
    mu = data[:n_train].mean(axis=0)
    sd = data[:n_train].std(axis=0)
    data = (data - mu) / (sd + 1e-6)
    return {
        "train": data[:n_train],
        "valid": data[n_train : n_train + n_valid],
        "test": data[n_train + n_valid :],
        "num_features": data.shape[1],
        "synthetic": False,
    }


def uci_electricity(data_path=None, *, num_series: int = 8, length: int = 10_000, seed: int = 0):
    """BASELINE.md config 4: multivariate forecasting.

    Real data: point ``data_path`` at ``LD2011_2014.txt`` (or a directory
    containing it) — the UCI ElectricityLoadDiagrams20112014 CSV. Synthetic
    stand-in otherwise: mixtures of sinusoids (daily/weekly periods) + AR(1)
    noise, one column per 'customer', normalised per-series."""
    uci_file = _resolve_uci_file(data_path)
    if uci_file is not None:
        return _uci_real(uci_file, num_series=num_series)
    if data_path:
        raise _no_files(data_path, "LD2011_2014.txt")
    rng = np.random.RandomState(seed)
    t = np.arange(length, dtype=np.float32)
    series = []
    for i in range(num_series):
        daily = np.sin(2 * np.pi * t / 24 + rng.uniform(0, 6.28))
        weekly = 0.5 * np.sin(2 * np.pi * t / (24 * 7) + rng.uniform(0, 6.28))
        noise = np.zeros(length, np.float32)
        for k in range(1, length):
            noise[k] = 0.8 * noise[k - 1] + 0.1 * rng.randn()
        s = (1 + 0.3 * i) * daily + weekly + noise
        series.append(s)
    data = np.stack(series, axis=1).astype(np.float32)  # [length, num_series]
    n_train = int(length * 0.8)
    n_valid = int(length * 0.1)
    # train-split statistics only (no valid/test leakage), as in _uci_real
    mu = data[:n_train].mean(axis=0)
    sd = data[:n_train].std(axis=0)
    data = (data - mu) / (sd + 1e-6)
    return {
        "train": data[:n_train],
        "valid": data[n_train : n_train + n_valid],
        "test": data[n_train + n_valid :],
        "num_features": num_series,
        "synthetic": True,
    }


DATASETS = {
    "ptb_char": ptb_char,
    "wikitext2": wikitext2_word,
    "wikitext103": wikitext103_word,
    "imdb": imdb,
    "uci_electricity": uci_electricity,
}


def get_dataset(name: str, data_path: str | None = None, **kw):
    if name not in DATASETS:
        raise ValueError(f"unknown dataset {name!r}; have {sorted(DATASETS)}")
    return DATASETS[name](data_path, **kw)
