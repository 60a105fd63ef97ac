"""The comparison that decides ``correct``.

Three numbers, each beside its limit (``LIMITS``; ``PERF.md`` gives the
readings each limit was set from):

``missing``     requests due in the window that never came back, or came
                back shed, rejected or failed.  Exact: limit 0.
``and_wrong``   ``and`` answers whose docid set is not the reference's.
                Exact: limit 0.  Every ``and`` answer of the window is
                compared.
``ranked_gap``  over a sample of the ranked answers drawn from the seed,
                the longest queries in it: the widest relative gap between
                a served score and the reference's score at the same rank,
                or the reference's exact score of the served doc.  A served
                list of the wrong length, or a doc that does not match the
                query, reads 1.
"""

from __future__ import annotations

import numpy as np

from .traffic import SAMPLE, rng_for

LIMITS = {"missing": 0, "and_wrong": 0, "ranked_gap": 1e-10}


def ranked_sample(recs: list, n: int, seed: int) -> list:
    """Up to ``n`` served ranked records drawn from the seed, always
    including the ones with the most terms."""
    ranked = [r for r in recs if r.query.mode != "and" and r.served]
    if len(ranked) <= n:
        return ranked
    longest = max(len(r.query.terms) for r in ranked)
    head = [r for r in ranked
            if len(r.query.terms) == longest][:max(1, n // 4)]
    rest = [r for r in ranked if r not in head]
    pick = rng_for(seed, SAMPLE).choice(len(rest), size=n - len(head),
                                        replace=False)
    return head + [rest[i] for i in sorted(pick)]


def ranked_gap(ref, terms, mode: str, k: int, got) -> float:
    """Widest relative score gap of one ranked answer (1 when malformed)."""
    want = ref.answer(terms, mode, k)
    if len(got) != len(want):
        return 1.0
    need_all = mode == "and_scored"
    gap = 0.0
    seen = set()
    for (doc, score), (_, wscore) in zip(got, want):
        doc = int(doc)
        exact = ref.score_of(terms, doc)
        held = [t for t in terms if t in ref.postings
                and _holds(ref.postings[t][0], doc)]
        if doc in seen or not held or (need_all and len(held) < len(terms)):
            return 1.0
        seen.add(doc)
        base = max(abs(wscore), 1e-300)
        gap = max(gap, abs(score - wscore) / base, abs(score - exact) / base)
    return float(gap)


def _holds(ids: np.ndarray, doc: int) -> bool:
    i = int(np.searchsorted(ids, doc))
    return i < len(ids) and int(ids[i]) == doc


def readings(ref, recs: list, seed: int, n_ranked: int) -> dict:
    """The three compared numbers for the window's records ``recs``."""
    missing = sum(not r.served for r in recs)
    and_wrong = 0
    for r in recs:
        if r.query.mode == "and" and r.served:
            want = ref.answer(r.query.terms, "and", r.query.k)
            got = np.asarray(r.result)
            if got.shape != want.shape or not np.array_equal(got, want):
                and_wrong += 1
    gap = 0.0
    for r in ranked_sample(recs, n_ranked, seed):
        gap = max(gap, ranked_gap(ref, r.query.terms, r.query.mode,
                                  r.query.k, r.result))
    return {"missing": missing, "and_wrong": and_wrong, "ranked_gap": gap}


def control_readings(ref, control, recs: list, seed: int,
                     n_ranked: int) -> dict:
    """The same numbers with the control's answers put in the program's
    place, for the same requests."""
    swapped = []
    for r in recs:
        c = type(r)(r.query, r.t_due, r.t_submit, r.t_done,
                    control.answer(r.query.terms, r.query.mode, r.query.k),
                    None)
        swapped.append(c)
    return readings(ref, swapped, seed, n_ranked)


def verdict(read: dict) -> tuple:
    """``(correct, lines)``: each number beside its limit."""
    ok = all(read[k] <= LIMITS[k] for k in LIMITS)
    lines = [f"check {k}: {read[k]!r} (limit {LIMITS[k]!r})" for k in LIMITS]
    return ok, lines
