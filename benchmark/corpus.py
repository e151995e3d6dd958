"""A word corpus from a seed: no network, the same seed gives the same files.

A copy of `chip_smoke.write_corpus` (the original is listed in PERF.md, Open
questions). ``wiki.{train,valid,test}.tokens`` is the pattern the program's
`data/corpus.py` resolves for the wikitext datasets. Words are drawn Zipf-like
so that a few optimizer steps already lower the loss, and every type occurs
in the train split, so the loader's vocabulary cap (50,000 / 33,278) binds.
"""

from __future__ import annotations

import os

import numpy as np


def write_corpus(directory: str, *, word_types: int, train_tokens: int,
                 seed: int) -> None:
    os.makedirs(directory, exist_ok=True)
    rng = np.random.default_rng(seed)
    words = np.array([f"w{i:05d}" for i in range(word_types)])
    p = 1.0 / np.arange(1, word_types + 1)
    p /= p.sum()
    for split, n in (("train", train_tokens),
                     ("valid", max(train_tokens // 10, 1)),
                     ("test", max(train_tokens // 10, 1))):
        ids = rng.choice(word_types, size=n, p=p)
        if split == "train":
            ids[rng.permutation(n)[:word_types]] = np.arange(word_types)
        with open(os.path.join(directory, f"wiki.{split}.tokens"), "w") as f:
            for i in range(0, n, 32):
                f.write(" ".join(words[ids[i:i + 32]]) + "\n")
