"""mimic2-hemo: the paper's MIMIC II analytic, served on one chip.

``setup`` makes the cohort from the seed, registers it through ``connect()``
(waveforms on the array engine, the patients table on the columnar engine,
the notes on the key-value engine), trains each probe's query and serves it
warm.  A request is the analytic for one of the probes, submitted to the
session's ``QueryServer``: Haar -> per-band histograms -> TF-IDF -> kNN.
"""
from __future__ import annotations

import time

import numpy as np

import datagen
import refs

WARM_SERVES = 2       # warm serves of each probe's query after training


def _queries(session, cfg):
    arr = session.islands.array
    out = []
    for i in range(cfg["probes"]):
        coeffs = arr.haar("waves", levels=cfg["levels"])
        hist = arr.bin_hist(coeffs, nbins=cfg["nbins"], levels=cfg["levels"])
        out.append(arr.knn(arr.tfidf(hist), f"probe_{i}", k=cfg["k"]))
    return out


class System:
    """The served session, the query of each probe, and the host copy of
    the data that the reference checks against."""

    def __init__(self, cfg, ctx):
        import jax.numpy as jnp
        from repro.core import connect
        from repro.core.tables import ColumnarTable, COOMatrix, DenseTensor

        n, t, p = cfg["patients"], cfg["samples"], cfg["probes"]
        t0 = time.perf_counter()
        seq = datagen.seed_sequence(ctx.seed, 1)
        s_waves, s_patients, s_notes = seq.spawn(3)
        waves, _ = datagen.ecg_waveforms(n + p, t, s_waves,
                                         cfg["deterioration_frac"])
        probe_hist = refs.wave_hist(waves[n:], cfg["levels"],
                                    cfg["nbins"]).astype(np.float32)
        waves = waves[:n]
        patients = datagen.patients_columns(n, s_patients)
        rows, cols, vals = datagen.notes_triples(
            n, s_notes, cfg["vocab"], cfg["terms_per_note"], cfg["topics"])
        ctx.log("setup", phase="data", seconds=time.perf_counter() - t0)

        t0 = time.perf_counter()
        self.session = connect(trace=ctx.trace, **cfg["connect"])
        s = self.session
        s.register("waves", DenseTensor(jnp.asarray(waves)),
                   engine="dense_array")
        for i in range(p):
            s.register(f"probe_{i}", DenseTensor(jnp.asarray(probe_hist[i:i + 1])),
                       engine="dense_array")
        s.register("patients", ColumnarTable(
            {k: jnp.asarray(v) for k, v in patients.items()}),
            engine="columnar")
        s.register("notes", COOMatrix(jnp.asarray(rows), jnp.asarray(cols),
                                      jnp.asarray(vals),
                                      (n, cfg["vocab"])), engine="kv_sparse")
        s.catalog["waves"].obj.data.block_until_ready()
        ctx.log("setup", phase="register", seconds=time.perf_counter() - t0)

        t0 = time.perf_counter()
        self.queries = _queries(s, cfg)
        self.train_reports, self.train_walls = [], []
        for q in self.queries:
            t = time.perf_counter()
            self.train_reports.append(s.execute(q, mode="training"))
            self.train_walls.append(time.perf_counter() - t)
        ctx.log("setup", phase="train", seconds=time.perf_counter() - t0,
                plans=[r.plan_key for r in self.train_reports],
                train_seconds=[r.seconds for r in self.train_reports])
        t0 = time.perf_counter()
        self.server = s.server()
        for q in self.queries:
            for _ in range(WARM_SERVES):
                self.serve_query(q)
        ctx.log("setup", phase="warm", seconds=time.perf_counter() - t0)
        self.host = {"waves": waves, "probe_hist": probe_hist}

    def serve_query(self, q):
        rep = self.server.submit(q)
        data = rep.result.data
        data.block_until_ready()
        return rep, data

    def serve(self, variant: int):
        rep, data = self.serve_query(self.queries[variant])
        return rep, np.asarray(data)

    @staticmethod
    def to_host(answer):
        return np.asarray(answer)

    def close(self):
        self.session.close()
        self.session = self.server = self.queries = None


def setup(cfg, ctx) -> System:
    return System(cfg, ctx)


def reference(cfg, host, answers):
    """The widest relative gap by which a served neighbour's reference
    score falls below the reference's k-th best, and the count of answers
    that are not k distinct patients."""
    w = refs.tfidf(refs.wave_hist(host["waves"], cfg["levels"], cfg["nbins"]))
    scores = w @ host["probe_hist"].astype(np.float64).T      # (n, probes)
    gap, bad = 0.0, 0
    for v, idx in answers:
        idx = np.asarray(idx).ravel()
        if not refs.knn_answer_ok(idx, cfg["patients"], cfg["k"]):
            bad += 1
            continue
        gap = max(gap, refs.knn_gaps(scores[:, v], idx, cfg["k"]))
    lim = cfg["limits"]
    return {"knn_gap": (gap, float(lim["knn_gap"])),
            "bad_answers": (float(bad), float(lim["bad_answers"]))}


def control(cfg, host):
    """The reference put in the program's place at one precision below the
    configuration's float32: every value of the waveforms, the Haar
    transform, the TF-IDF weights and the scores rounded to bfloat16
    (``reduce_precision``, which no backend may elide), histogram counts
    exact, score sums accumulated in float32 as the MXU does.  Runs on the
    chip; returns one answer per probe."""
    import jax
    import jax.numpy as jnp

    levels, nbins, k = cfg["levels"], cfg["nbins"], cfg["k"]
    inv = np.float32(1.0 / np.sqrt(2.0))

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def haar(x):
        a, det = x, []
        for _ in range(levels):
            e, o = a[:, 0::2], a[:, 1::2]
            det.append(bf16(bf16(e - o) * inv))
            a = bf16(bf16(e + o) * inv)
        return jnp.concatenate([a] + det[::-1], axis=1)

    def hist(c):
        out = []
        for off, ln in refs.bands(c.shape[1], levels):
            u = bf16(bf16(bf16(c[:, off:off + ln] + 3.0) / 6.0) * nbins)
            idx = jnp.clip(jnp.floor(u), 0, nbins - 1).astype(jnp.int32)
            out.append(jnp.stack([jnp.sum(idx == b, axis=1)
                                  for b in range(nbins)], axis=1))
        return jnp.concatenate(out, axis=1)

    @jax.jit
    def weights(x):
        rows = 256 if x.shape[0] % 256 == 0 else x.shape[0]
        tf = jax.lax.map(lambda blk: hist(haar(bf16(blk))),
                         x.reshape(-1, rows, x.shape[1])).reshape(
                             x.shape[0], -1).astype(jnp.float32)
        idf = bf16(jnp.log(bf16(tf.shape[0] / (1.0 + jnp.sum(tf > 0, axis=0)
                                               ))) + 1.0)
        w = bf16(bf16(tf) * idf)
        norm = bf16(jnp.sqrt(bf16(jnp.sum(bf16(w * w), axis=1,
                                          keepdims=True))))
        return bf16(w / jnp.maximum(norm, 1e-9))

    @jax.jit
    def top(w, probe):
        s = jnp.dot(w, bf16(probe), precision=jax.lax.Precision.HIGHEST)
        return jax.lax.top_k(s, k)[1]

    w = weights(jnp.asarray(host["waves"]))
    return [(i, np.asarray(top(w, jnp.asarray(host["probe_hist"][i]))))
            for i in range(cfg["probes"])]
