"""Plain reference answers, computed from the raw seeded posting lists.

Nothing here imports the program or reads what it built: the corpus comes
from ``chipbench.corpus`` for the same configuration and seed, before any
codec.

* ``and``: the docids present in every term's list (numpy set
  intersection, rarest list first).
* ``or`` / ``and_scored``: Okapi BM25 (k1 = 1.2, b = 0.75, idf =
  ln(1 + (N - df + 0.5) / (df + 0.5))) in float64, summed over the query's
  terms in query order, over the union (``or``) or the intersection
  (``and_scored``) of the lists; the top k by descending score, ties by
  ascending docid.

The controls are what a later change might be tempted to serve in place of
the exact answer; each must come out as not correct:

* ``and``: an intersection over a doc space folded in half (docid >> 1), as
  a bitmap at half the resolution would give;
* ranked: the same BM25 computed in float32.
"""

from __future__ import annotations

import numpy as np

K1, B = 1.2, 0.75


class Reference:
    def __init__(self, doclen: np.ndarray, postings: dict,
                 dtype=np.float64):
        self.postings = postings
        self.n_docs = len(doclen)
        self.doclen = doclen
        self.avdl = float(np.mean(doclen)) if len(doclen) else 1.0
        self.dtype = dtype
        self._scores: dict = {}
        self._answers: dict = {}

    def term_scores(self, t: int) -> np.ndarray:
        """BM25 impact of term ``t`` for each posting of its list."""
        s = self._scores.get(t)
        if s is None:
            ids, tfs = self.postings[t]
            f = self.dtype
            df = f(len(ids))
            n = f(self.n_docs)
            idf = np.log(f(1.0) + (n - df + f(0.5)) / (df + f(0.5)))
            tf = tfs.astype(f)
            norm = f(1 - B) + f(B) * self.doclen[ids].astype(f) / f(self.avdl)
            s = self._scores[t] = idf * tf * f(K1 + 1) / (tf + f(K1) * norm)
        return s

    def conjunction(self, terms) -> np.ndarray:
        lists = sorted((self.postings[t][0] for t in terms), key=len)
        out = lists[0]
        for ids in lists[1:]:
            pos = np.minimum(np.searchsorted(ids, out), len(ids) - 1)
            out = out[ids[pos] == out]
        return out

    def answer(self, terms, mode: str, k: int):
        """``and``: sorted uint32 docids; ranked: ``[(docid, score), ...]``.
        Computed once per distinct query."""
        key = (tuple(terms), mode, k)
        if key not in self._answers:
            self._answers[key] = self._answer(list(terms), mode, k)
        return self._answers[key]

    def _answer(self, terms, mode: str, k: int):
        terms = [t for t in terms if t in self.postings]
        if mode == "and":
            return (self.conjunction(terms) if terms
                    else np.zeros(0, np.uint32))
        if not terms:
            return []
        if mode == "or":
            acc = np.zeros(self.n_docs, self.dtype)
            for t in terms:
                acc[self.postings[t][0]] += self.term_scores(t)
            docs = np.flatnonzero(acc > 0)
            scores = acc[docs]
        elif mode == "and_scored":
            docs = self.conjunction(terms).astype(np.int64)
            scores = np.zeros(len(docs), self.dtype)
            for t in terms:
                ids = self.postings[t][0]
                scores += self.term_scores(t)[np.searchsorted(ids, docs)]
        else:
            raise ValueError(f"unknown mode {mode!r}")
        return top_k(docs, scores, k)

    def score_of(self, terms, doc: int) -> float:
        """The exact score of one doc for ``terms`` (0 where it holds none)."""
        s = 0.0
        for t in terms:
            if t not in self.postings:
                continue
            ids = self.postings[t][0]
            i = int(np.searchsorted(ids, doc))
            if i < len(ids) and ids[i] == doc:
                s += float(self.term_scores(t)[i])
        return s


def top_k(docs: np.ndarray, scores: np.ndarray, k: int) -> list:
    if len(docs) > k:
        kth = np.partition(scores, len(scores) - k)[len(scores) - k]
        keep = np.flatnonzero(scores >= kth)
        docs, scores = docs[keep], scores[keep]
    order = np.lexsort((docs, -scores))[:k]
    return [(int(docs[i]), float(scores[i])) for i in order]


class Control(Reference):
    """The reference at the control's precision: float32 BM25 for the
    ranked modes, and ``and`` answered over a doc space folded in half."""

    def __init__(self, doclen, postings):
        super().__init__(doclen, postings, dtype=np.float32)

    def _answer(self, terms, mode: str, k: int):
        if mode != "and":
            return super()._answer(terms, mode, k)
        lists = sorted((self.postings[t][0] for t in terms
                        if t in self.postings), key=len)
        if not lists:
            return np.zeros(0, np.uint32)
        keep = np.ones(len(lists[0]), bool)
        v = lists[0] >> 1
        for ids in lists[1:]:
            half = ids >> 1
            pos = np.minimum(np.searchsorted(half, v), len(half) - 1)
            keep &= half[pos] == v
        return lists[0][keep]
