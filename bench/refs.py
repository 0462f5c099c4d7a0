"""Plain numpy references for the benchmark's configurations.

Copied from the numpy references of ``chip_smoke.py`` (``np_haar``,
``np_bin_hist``, ``np_tfidf``) and written apart from the program: nothing
here imports ``repro``.  Every reference computes in float64 on the host, in
blocks of rows so that it fits beside the program's data.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 512
THREADS = 8


def bands(t: int, levels: int):
    """(offset, length) of [a_L, d_L, ..., d_1] in a Haar row of length t."""
    out, off = [(0, t >> levels)], t >> levels
    for lev in range(levels, 0, -1):
        out.append((off, t >> lev))
        off += t >> lev
    return out


def haar(x: np.ndarray, levels: int) -> np.ndarray:
    """Multi-level Haar transform of each row: [a_L, d_L, ..., d_1]."""
    a, details, inv = x.astype(np.float64), [], 1.0 / np.sqrt(2.0)
    for _ in range(levels):
        e, o = a[:, 0::2], a[:, 1::2]
        details.append((e - o) * inv)
        a = (e + o) * inv
    return np.concatenate([a] + details[::-1], axis=1)


def bin_hist(c: np.ndarray, nbins: int, levels: int, lo: float = -3.0,
             hi: float = 3.0) -> np.ndarray:
    """Per-band histograms of Haar coefficients, values outside [lo, hi)
    counted in the end bins."""
    n, t = c.shape
    hist = np.zeros((n, (levels + 1) * nbins))
    rows = nbins * np.arange(n)[:, None]
    for b, (off, ln) in enumerate(bands(t, levels)):
        u = (c[:, off:off + ln] - lo) / (hi - lo) * nbins
        idx = np.clip(np.floor(u), 0, nbins - 1).astype(np.int64)
        hist[:, b * nbins:(b + 1) * nbins] = np.bincount(
            (idx + rows).ravel(), minlength=n * nbins).reshape(n, nbins)
    return hist


def tfidf(tf: np.ndarray) -> np.ndarray:
    """Unit-norm TF-IDF rows of a count matrix."""
    n = tf.shape[0]
    idf = np.log(n / (1.0 + (tf > 0).sum(axis=0))) + 1.0
    w = tf * idf
    return w / np.maximum(np.linalg.norm(w, axis=1, keepdims=True), 1e-9)


def wave_hist(waves: np.ndarray, levels: int, nbins: int) -> np.ndarray:
    """``bin_hist(haar(waves))`` in blocks of rows, on a few threads."""
    n = waves.shape[0]
    out = np.empty((n, (levels + 1) * nbins))

    def one(lo):
        hi = min(n, lo + BLOCK_ROWS)
        out[lo:hi] = bin_hist(haar(waves[lo:hi], levels), nbins, levels)

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(one, range(0, n, BLOCK_ROWS)))
    return out


def knn_gaps(scores: np.ndarray, idx: np.ndarray, k: int) -> float:
    """How far the returned neighbours fall below the reference's k-th best
    score, the widest gap as a share of that score (0 when every neighbour
    is among the reference's k best)."""
    kth = np.partition(scores, -k)[-k]
    return float(max(0.0, np.max((kth - scores[idx]) / abs(kth))))


def knn_answer_ok(idx: np.ndarray, n: int, k: int) -> bool:
    """k distinct row indices, each in range."""
    idx = np.asarray(idx).ravel()
    return (idx.size == k and len(set(idx.tolist())) == k
            and bool(np.all((idx >= 0) & (idx < n))))

