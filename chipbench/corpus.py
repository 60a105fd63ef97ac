"""Seeded posting lists of a configuration's corpus shape.

A copy of the Zipf corpus of ``repro.data.synth.make_dataset`` /
``make_corpus`` (``DATASETS`` shape parameters now come from the
configuration file), kept here so that the data the program indexes and the
data the reference reads come from the benchmark, not from the program.

Per term rank ``r`` (0-based), ``df = clip(n_docs * 0.6 / (r + 1) ** (s -
0.05), 8, n_docs)`` docids drawn without replacement, and term frequencies
``min(geometric(0.35), 4096)``.  Every document has the constant length
``doclen``.

The lists are drawn once from the configuration's ``lists_seed``, and a
run's seed deals them to term ids (``term_ids``): every seed indexes the
same lists, so the same sizes, codec extents and intersections, under
other term ids.
"""

from __future__ import annotations

import zlib

import numpy as np


def _shape_key(cfg: dict) -> int:
    # crc32 of the shape name keeps two shapes at one seed apart
    return zlib.crc32(cfg["shape"].encode())


def term_ids(cfg: dict, seed: int) -> np.ndarray:
    """The term id that holds the list of each rank under ``seed``: a
    seeded permutation of ``range(n_lists)``; any integer seed, however
    large, maps to one stream."""
    n = min(int(cfg["n_lists"]), int(cfg["n_terms_sampled"]))
    rng = np.random.default_rng([int(seed) % (1 << 64), _shape_key(cfg), 1])
    return rng.permutation(n)


def make_corpus(cfg: dict, seed: int):
    """``(doclen, postings)`` for configuration ``cfg`` and ``seed``:
    ``doclen`` int64 of length ``n_docs``, ``postings`` term -> (docids
    uint32 sorted ascending, tfs uint32 >= 1) for the ``n_lists`` highest-df
    terms of the shape, the list of rank ``r`` under ``term_ids(cfg,
    seed)[r]``."""
    n_docs, s = int(cfg["n_docs"]), float(cfg["zipf_s"])
    n_terms = int(cfg["n_terms_sampled"])
    rng = np.random.default_rng([int(cfg["lists_seed"]) % (1 << 64),
                                 _shape_key(cfg)])
    ranks = np.arange(1, n_terms + 1, dtype=np.float64)
    df = np.minimum((n_docs * 0.6) / ranks ** (s - 0.05), n_docs)
    df = np.maximum(df.astype(np.int64), 8)
    ids_of = term_ids(cfg, seed)
    postings = {}
    for r in range(len(ids_of)):
        ids = np.sort(rng.choice(n_docs, size=int(df[r]), replace=False))
        tf = np.minimum(rng.geometric(0.35, size=len(ids)), 4096)
        postings[int(ids_of[r])] = (ids.astype(np.uint32), tf.astype(np.uint32))
    doclen = np.full(n_docs, int(cfg["doclen"]), np.int64)
    return doclen, dict(sorted(postings.items()))


def n_postings(postings: dict) -> int:
    return int(sum(len(ids) for ids, _ in postings.values()))
