"""Seeded, vectorised data for the benchmark's configurations.

A vectorised copy of ``repro.data.synthetic``'s MIMIC II-shaped generators
(the same distributions, drawn in row blocks rather than per patient).
Everything is made on the host with numpy from ``--seed``, so the plain
reference sees exactly the bytes the program was given.  Row blocks draw from independent child streams of one
``SeedSequence``, so the result does not depend on how many threads fill it.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 256
THREADS = 8


def seed_sequence(seed: int, *salt: int) -> np.random.SeedSequence:
    """A SeedSequence for any whole-number seed (negative ones included)."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0), *salt])


def fill_rows(out: np.ndarray, seq: np.random.SeedSequence, make) -> None:
    """``out[lo:hi] = make(rng, lo, hi)`` for each block of ``BLOCK_ROWS``
    rows, each block with its own child stream of ``seq``, on a few
    threads (numpy releases the GIL in its kernels)."""
    n = out.shape[0]
    blocks = [(lo, min(n, lo + BLOCK_ROWS)) for lo in range(0, n, BLOCK_ROWS)]
    rngs = [np.random.default_rng(s) for s in seq.spawn(len(blocks))]

    def one(i):
        lo, hi = blocks[i]
        out[lo:hi] = make(rngs[i], lo, hi)

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(one, range(len(blocks))))


def ecg_waveforms(n: int, t: int, seq: np.random.SeedSequence,
                  deterioration_frac: float = 0.3):
    """(n, t) float32 ECG-like waveforms and (n,) int32 labels, with the
    distributions of ``repro.data.synthetic.ecg_waveforms``: quasi-periodic
    beats plus noise; deteriorating patients add amplitude decay, a slow
    drift and a random walk."""
    ts = np.arange(t, dtype=np.float32)
    waves = np.empty((n, t), np.float32)
    labels = np.empty(n, np.int32)

    def make(rng, lo, hi):
        m = hi - lo
        lab = (rng.random(m) < deterioration_frac).astype(np.int32)
        labels[lo:hi] = lab
        rate = rng.uniform(0.035, 0.055, (m, 1)).astype(np.float32)
        phase = rng.uniform(0, 2 * np.pi, (m, 1)).astype(np.float32)
        w = np.float32(2 * np.pi) * rate * ts
        sig = (np.sin(w + phase) + np.float32(0.4) * np.sin(2 * w + 2 * phase)
               + np.float32(0.15) * np.sin(3 * w))
        bad = np.flatnonzero(lab)
        if bad.size:
            k = bad.size
            tau = (t * rng.uniform(0.7, 1.4, (k, 1))).astype(np.float32)
            freq = rng.uniform(1.5, 4.0, (k, 1)).astype(np.float32)
            walk = np.cumsum(rng.standard_normal((k, t), np.float32)
                             * np.float32(0.02), axis=1)
            drift = np.float32(0.35) * np.sin(
                np.float32(2 * np.pi) * freq * ts / np.float32(t))
            sig[bad] = sig[bad] * np.exp(-ts / tau) + drift + walk
        sig += rng.standard_normal((m, t), np.float32) * np.float32(0.12)
        return sig

    fill_rows(waves, seq, make)
    return waves, labels


def patients_columns(n: int, seq: np.random.SeedSequence) -> dict:
    """The patients table of ``repro.data.synthetic.patients_table``."""
    rng = np.random.default_rng(seq)
    return {
        "patient_id": np.arange(n, dtype=np.int32),
        "age": rng.integers(18, 95, n).astype(np.int32),
        "gender": rng.integers(0, 2, n).astype(np.int32),
        "icu_type": rng.integers(0, 4, n).astype(np.int32),
        "heart_rate_mean": rng.normal(82, 14, n).astype(np.float32),
        "sapsi": rng.integers(0, 32, n).astype(np.int32),
    }


def notes_triples(n: int, seq: np.random.SeedSequence, vocab: int = 4096,
                  terms_per_note: int = 60, n_topics: int = 8):
    """(rows, cols, vals) of the notes matrix of
    ``repro.data.synthetic.notes_coo``: each note holds its topic's terms
    and ``terms_per_note // 3`` random ones, each term once, with Poisson
    counts plus one."""
    rng = np.random.default_rng(seq)
    topic = rng.integers(0, n_topics, n)
    base = rng.zipf(1.5, size=(n_topics, terms_per_note)) % vocab
    extra = rng.integers(0, vocab, (n, terms_per_note // 3))
    terms = np.concatenate([base[topic], extra], axis=1)
    terms.sort(axis=1)
    keep = np.ones(terms.shape, bool)
    keep[:, 1:] = terms[:, 1:] != terms[:, :-1]
    rows = np.broadcast_to(np.arange(n)[:, None], terms.shape)[keep]
    cols = terms[keep]
    vals = rng.poisson(2.0, cols.shape).astype(np.float32) + 1.0
    return rows.astype(np.int32), cols.astype(np.int32), vals

