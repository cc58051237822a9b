"""Decide ``correct`` from the answers the timed window produced.

Every answered request is held to what the configuration guarantees: k
distinct passages of the corpus, in descending order of score, each score
the float32 inner product of *its own* query with that passage. The plain
reference recomputes each score from the seeded corpus; the widest gap,
``score_err``, is compared with the configuration's ``score_err_limit``
(between the program's readings and the control's; see PERF.md).

Which passages come back is decided before the rescore: routing, the
in-cluster candidate windows, the sketch pre-filter and the code pass. The
mean recall@k of every checked answer against the reference's exact top-k
has to reach the configuration's ``recall_floor`` (between the program's
readings and those of faults planted in those layers; see PERF.md).

A request that got no answer, was shed, was answered degraded
(compressed-only), or whose answer breaks one of those rules is ``failed``.
``correct`` holds when no request failed, and the widest score gap and the
mean recall are within their limits. In ``limits()`` a number whose name
ends in ``_min`` has to be at least its limit, every other at most.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Verdict:
    attempted: int
    failed: int
    unanswered: int
    refused: int  # shed or degraded
    malformed: int  # wrong length, ids out of range or repeated, bad order
    wrong: int  # a score further than the limit from the reference's
    score_err: float  # widest |served - reference| score over all answers
    score_err_limit: float
    recall: np.ndarray  # per request, recall@k against exact top-k (NaN: none)
    recall_floor: float

    @property
    def recall_mean(self) -> float:
        """Mean recall@k over every checked answer (0 when none was)."""
        r = self.recall[~np.isnan(self.recall)]
        return float(r.mean()) if r.size else 0.0

    @property
    def correct(self) -> bool:
        return (self.failed == 0 and self.score_err <= self.score_err_limit
                and self.recall_mean >= self.recall_floor)

    def limits(self) -> dict:
        """Each number compared, beside its limit."""
        return {
            "score_err": {"value": self.score_err, "limit": self.score_err_limit},
            "recall_at_10_min": {"value": self.recall_mean,
                                 "limit": self.recall_floor},
            "unanswered": {"value": self.unanswered, "limit": 0},
            "shed_or_degraded": {"value": self.refused, "limit": 0},
            "malformed": {"value": self.malformed, "limit": 0},
            "wrong": {"value": self.wrong, "limit": 0},
        }


def answer_arrays(answers: list, k: int):
    """``(ids (n, k), scores (n, k), answered, refused)`` from engine results."""
    n = len(answers)
    ids = np.full((n, k), -1, np.int64)
    scores = np.full((n, k), np.nan, np.float32)
    answered = np.zeros(n, bool)
    refused = np.zeros(n, bool)
    for i, a in enumerate(answers):
        if a is None or not hasattr(a, "ids"):
            refused[i] = a is not None  # Shed / EVICTED: an answer that refuses
            continue
        if getattr(a, "degraded", False):
            refused[i] = True
            continue
        a_ids = np.asarray(a.ids).reshape(-1)
        a_sc = np.asarray(a.scores, np.float32).reshape(-1)
        if a_ids.shape != (k,) or a_sc.shape != (k,):
            continue  # malformed: left with -1 ids
        ids[i], scores[i] = a_ids, a_sc
        answered[i] = True
    return ids, scores, answered, refused


def well_formed(ids: np.ndarray, scores: np.ndarray, n_corpus: int) -> np.ndarray:
    """Per row: ids in range and distinct, scores finite and descending."""
    in_range = np.all((ids >= 0) & (ids < n_corpus), axis=1)
    srt = np.sort(ids, axis=1)
    distinct = np.all(srt[:, 1:] != srt[:, :-1], axis=1)
    finite = np.all(np.isfinite(scores), axis=1)
    ordered = np.all(scores[:, 1:] <= scores[:, :-1], axis=1)
    return in_range & distinct & finite & ordered


def judge(reference, corpus, queries: np.ndarray, answers: list, *, k: int,
          limit: float, recall_floor: float) -> Verdict:
    """Hold every answer against the reference. ``queries[i]`` is the query
    request ``i`` sent, ``answers[i]`` what it got back."""
    n = len(answers)
    ids, scores, answered, refused = answer_arrays(answers, k)
    unanswered = ~answered & ~refused
    ok = answered & well_formed(ids, scores, corpus.shape[0])
    malformed = answered & ~ok
    row_err = np.zeros(n)
    recall = np.full(n, np.nan)
    if ok.any():
        q = queries[ok]
        ref_scores = reference.scores_of(corpus, q, ids[ok])
        row_err[ok] = np.max(np.abs(ref_scores - scores[ok]), axis=1)
        truth, _ = reference.exact_topk(corpus, q, k)
        recall[ok] = [len(set(a) & set(b)) / k for a, b in zip(ids[ok], truth)]
    wrong = ok & (row_err > limit)
    failed = int(unanswered.sum() + refused.sum() + malformed.sum() + wrong.sum())
    return Verdict(attempted=n, failed=failed, unanswered=int(unanswered.sum()),
                   refused=int(refused.sum()), malformed=int(malformed.sum()),
                   wrong=int(wrong.sum()), score_err=float(row_err.max(initial=0.0)),
                   score_err_limit=float(limit), recall=recall,
                   recall_floor=float(recall_floor))
