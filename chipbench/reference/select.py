"""Plain float64 reference of GRAFT's selection decision (NumPy, host).

From the rows' pooled hiddens ``A`` (K, d) and probe gradient embeddings
``E`` (K, d):

1. features ``V = U_r * sigma_r``: the top ``r = r_max`` left singular
   vectors of ``A`` scaled by their singular values, columns by
   decreasing singular value;
2. Fast MaxVol: for column ``j = 0..r-1`` pick the still-available row
   with the largest ``|W[i, j]|``, then eliminate column ``j`` from every
   other row (``W -= W[:, j] / W[p, j] * W[p, :]``);
3. prefix projection errors ``err_r = 1 - |Q_r^T g|^2 / |g|^2`` with
   ``g`` the rows' mean embedding and ``Q_r`` an orthonormal basis of the
   first ``r`` chosen rows' embeddings;
4. rank: the smallest candidate ``r`` with ``err_r <= eps``, else the
   largest candidate; weights ``1 / rank`` on the first ``rank`` pivots.

Besides the decision it reports its margins: the smallest relative gap
between the best and the second-best ``|W[i, j]|`` over the pivots up to
the rank, and the smallest distance from ``eps`` of the error of a candidate up
to the rank.

``shortfall`` judges another decision (the program's) by this
reference's own scores: how far each of its pivots up to its rank falls
short of the best available row, and how far its rank breaks the rule.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np


def features(A: np.ndarray, r: int) -> np.ndarray:
    A = np.asarray(A, np.float64)
    evals, evecs = np.linalg.eigh(A @ A.T)
    order = np.argsort(evals)[::-1][:r]
    return evecs[:, order] * np.sqrt(np.clip(evals[order], 0.0, None))


def fast_maxvol(V: np.ndarray, r: int):
    W = np.array(V, np.float64)
    avail = np.ones(W.shape[0], bool)
    pivots, margins = [], []
    for j in range(r):
        scores = np.where(avail, np.abs(W[:, j]), -1.0)
        order = np.argsort(scores)[::-1]
        p = int(order[0])
        best, second = scores[order[0]], max(scores[order[1]], 0.0)
        margins.append((best - second) / best if best > 0 else 0.0)
        row = W[p].copy()
        W = W - np.outer(W[:, j] / row[j], row)
        W[p] = row
        avail[p] = False
        pivots.append(p)
    return pivots, margins


def prefix_errors(E: np.ndarray, pivots: Sequence[int]) -> np.ndarray:
    E = np.asarray(E, np.float64)
    g = E.mean(axis=0)
    g = g / np.linalg.norm(g)
    errs = []
    for r in range(1, len(pivots) + 1):
        q, _ = np.linalg.qr(E[list(pivots[:r])].T)
        errs.append(1.0 - float(np.sum((q.T @ g) ** 2)))
    return np.clip(np.asarray(errs), 0.0, 1.0)


def decide(A: np.ndarray, E: np.ndarray, rset: Sequence[int],
           eps: float) -> Dict[str, Any]:
    r_max = max(rset)
    pivots, margins = fast_maxvol(features(A, r_max), r_max)
    errs = prefix_errors(E, pivots)
    rank = next((r for r in rset if errs[r - 1] <= eps), r_max)
    weights = [1.0 / rank if i < rank else 0.0 for i in range(r_max)]
    return {"pivots": pivots, "rank": rank, "weights": weights,
            "errors": errs.tolist(),
            "pivot_margin": float(min(margins[:rank])),
            "rank_margin": float(min(abs(errs[r - 1] - eps)
                                     for r in rset if r <= rank))}


def shortfall(A: np.ndarray, E: np.ndarray, rset: Sequence[int], eps: float,
              pivots: Sequence[int], rank: int) -> float:
    """The largest of: over the decision's pivots up to its rank, the
    relative shortfall ``(best - |W[p, j]|) / best`` of pivot ``p`` against
    the best still-available row, with ``W`` eliminated by the decision's
    own pivots; and how far, in projection error, the prefix errors of its
    pivots break the rank rule (an error at or under ``eps`` for a
    candidate under its rank, or one over ``eps`` at its rank). 0 for the
    reference's own decision; 1 for a pivot that is no row or was taken."""
    r_max = max(rset)
    W = features(A, r_max)
    avail = np.ones(W.shape[0], bool)
    piv = [int(p) for p in pivots[:rank]]
    worst = 0.0
    for j, p in enumerate(piv):
        if not 0 <= p < W.shape[0] or not avail[p]:
            return 1.0
        scores = np.where(avail, np.abs(W[:, j]), -1.0)
        best = scores.max()
        if best > 0:
            worst = max(worst, (best - scores[p]) / best)
        row = W[p].copy()
        W = W - np.outer(W[:, j] / row[j], row)
        W[p] = row
        avail[p] = False
    errs = prefix_errors(E, piv)
    for r in rset:
        if r < rank:
            worst = max(worst, eps - errs[r - 1])
        elif r == rank and rank != r_max:
            worst = max(worst, errs[r - 1] - eps)
    return float(worst)
