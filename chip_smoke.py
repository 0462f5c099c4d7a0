"""Smoke run of the polystore's served path on a TPU, checked against numpy.

One chip (the default): the paper's MIMIC II-shaped hemodynamic analytic
(Haar -> per-scale histograms -> TF-IDF -> kNN, the widths of
``benchmarks/fig5_polystore_analytic.py``) over 8,192 patients x 16,384
samples of f32 waveforms (512 MiB on the device), with the patients table
and the notes of ``mimic_like_dataset`` registered beside them.  It is run
through the entry points a user calls:

  * ``connect()`` -> ``Session.execute``: one training serve, then
    production serves from the plan cache;
  * ``execute_plan`` under the dense-only, columnar-only and hybrid
    placements;
  * ``QueryServer``: concurrent requests;
  * the paper's §III-C-2 cross-island query (RELATIONAL select -> ARRAY
    matmul) with A 4,096 x 4,096 and B 4,096 x 512.

Every answer is checked against a plain numpy reference computed on the
host (tolerances below).  Earlier output lines give the seconds and compile
seconds of each phase, the bytes resident on the device, the served plan
keys, the fallback counters (all must be 0) and proof that the dense
``haar``/``knn`` ops and the fused segments that served are compiled Pallas
(``tpu_custom_call`` in their lowered text).  The last line is the JSON
``{"ok": true, "device": {...}}`` — printed only when every check passed
on a TPU.

``--chips 4`` runs only the worker-pool path on a four-chip host: a
``connect(processes=4)`` session with the waveforms registered
``shards=4``, each worker pinned to a chip of its own, the Haar ->
histogram prefix of the analytic scatter-gathered over the four workers and
the whole analytic served by the pool — and the same answers from an
in-process session and from the numpy reference.

``--small`` shrinks every size for a rehearsal on the CPU backend; off a
TPU the script runs its phases and exits non-zero.  At full size it refuses
to start without a TPU.

Run:  python chip_smoke.py [--seed N] [--small] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from benchmarks.fig5_polystore_analytic import (K, LEVELS, NBINS,  # noqa: E402
                                                build_query, named_plans)
from repro.core import device  # noqa: E402

FULL = {"patients": 8192, "samples": 16384, "a": 4096, "b_cols": 512}
SMALL = {"patients": 64, "samples": 1024, "a": 128, "b_cols": 16}
CROSS_QUERY = ("ARRAY(matmul(RELATIONAL(select(A, column=value, lo=-0.5, "
               "hi=2.0)), B))")          # examples/quickstart.py
SELECT_LO, SELECT_HI = -0.5, 2.0
PRODUCTION_SERVES = 3
CONCURRENT_REQUESTS = 4

# -- tolerances against the numpy reference (f64 on the host) ---------------
# Haar: six levels of f32 sums of O(1) samples; f32 rounding stays < 1e-5.
HAAR_ATOL = 1e-4
# Histograms are computed from the system's own coefficients, so a count may
# only differ where a coefficient sits within EDGE_EPS (in bin units) of a
# bin edge: f32 versus f64 arithmetic can put it on either side.
EDGE_EPS = 1e-4
# TF-IDF weights (unit-norm rows) from the system's own histograms: f32
# log, sqrt and divide, which the TPU does not round correctly (its largest
# error measured on a v5e was 3.7e-6 on weights below 1).
TFIDF_RTOL, TFIDF_ATOL = 1e-4, 1e-5
# kNN: the reference scores every patient with the system's TF-IDF weights;
# each returned neighbour's score may fall below the reference k-th score
# by at most KNN_RTOL of it (f32 scores of unit-norm rows against counts).
KNN_RTOL = 1e-4
# Cross-island matmul: f32 accumulation over 4,096 terms of N(0,1) values.
MATMUL_RTOL, MATMUL_ATOL = 1e-5, 1e-3


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def emit(tag: str, **fields) -> None:
    print(f"{tag}: " + json.dumps(fields, default=str), flush=True)


# -- numpy reference (host, f64; independent of the code under test) ---------

def bands(t: int, levels: int):
    """(offset, length) of [a_L, d_L, ..., d_1] in a Haar row of length t."""
    out, off = [(0, t >> levels)], t >> levels
    for lev in range(levels, 0, -1):
        out.append((off, t >> lev))
        off += t >> lev
    return out


def np_haar(x: np.ndarray, levels: int) -> np.ndarray:
    a, details, inv = x.astype(np.float64), [], 1.0 / np.sqrt(2.0)
    for _ in range(levels):
        e, o = a[:, 0::2], a[:, 1::2]
        details.append((e - o) * inv)
        a = (e + o) * inv
    return np.concatenate([a] + details[::-1], axis=1)


def np_bin_hist(c: np.ndarray, nbins: int, levels: int, lo=-3.0, hi=3.0):
    """Per-band histograms, and per row the count of values within
    ``EDGE_EPS`` of an interior bin edge."""
    n, t = c.shape
    hist = np.zeros((n, (levels + 1) * nbins))
    near = np.zeros(n, np.int64)
    rows = nbins * np.arange(n)[:, None]
    for b, (off, ln) in enumerate(bands(t, levels)):
        u = (c[:, off:off + ln].astype(np.float64) - lo) / (hi - lo) * nbins
        idx = np.clip(np.floor(u), 0, nbins - 1).astype(np.int64)
        hist[:, b * nbins:(b + 1) * nbins] = np.bincount(
            (idx + rows).ravel(), minlength=n * nbins).reshape(n, nbins)
        edge = (np.abs(u - np.round(u)) < EDGE_EPS) & (u > 0.5) \
            & (u < nbins - 0.5)
        near += edge.sum(axis=1)
    return hist, near


def np_tfidf(tf: np.ndarray) -> np.ndarray:
    n = tf.shape[0]
    idf = np.log(n / (1.0 + (tf > 0).sum(axis=0))) + 1.0
    w = tf * idf
    return w / np.maximum(np.linalg.norm(w, axis=1, keepdims=True), 1e-9)


# -- checks --------------------------------------------------------------------

def check_haar(got, waves):
    err = float(np.max(np.abs(np.asarray(got, np.float64)
                              - np_haar(waves, LEVELS))))
    check(err <= HAAR_ATOL, f"haar max|err| {err} > {HAAR_ATOL}")
    return err


def check_hist(got, coeffs):
    want, near = np_bin_hist(np.asarray(coeffs), NBINS, LEVELS)
    got = np.asarray(got, np.float64)
    check(got.shape == want.shape, f"hist shape {got.shape} != {want.shape}")
    moved = np.abs(got - want).sum(axis=1)
    check(bool(np.all(moved <= 2 * near)),
          f"hist counts differ beyond bin-edge ties in "
          f"{int(np.sum(moved > 2 * near))} rows")
    return int(moved.sum())


def check_tfidf(got, hist):
    want = np_tfidf(np.asarray(hist, np.float64))
    err = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
    check(np.allclose(got, want, rtol=TFIDF_RTOL, atol=TFIDF_ATOL),
          f"tfidf max|err| {err}")
    return err


def check_knn(idx, weights, test_hist):
    """Returned neighbours against the reference k-th score; returns the
    smallest margin (score - kth) / |kth| over the neighbours."""
    idx = np.asarray(idx).ravel()
    scores = np.asarray(weights, np.float64) @ np.asarray(
        test_hist, np.float64).ravel()
    kth = np.sort(scores)[-K]
    check(idx.size == K and len(set(idx.tolist())) == K,
          f"knn returned {idx.tolist()}")
    margin = float(np.min((scores[idx] - kth) / abs(kth)))
    check(margin >= -KNN_RTOL, f"knn neighbour below the k-th score by "
                               f"{-margin:.3g} (relative) > {KNN_RTOL}")
    return margin


def check_matmul(got, a, b):
    want = np.where((a >= SELECT_LO) & (a <= SELECT_HI), a.astype(np.float64),
                    0.0) @ b.astype(np.float64)
    got = np.asarray(got, np.float64)
    err = float(np.max(np.abs(got - want)))
    check(got.shape == want.shape and np.allclose(
        got, want, rtol=MATMUL_RTOL, atol=MATMUL_ATOL),
        f"matmul max|err| {err}")
    return err


# -- phase clock (host seconds, and JAX backend-compile seconds inside) -------

_COMPILE = {"seconds": 0.0, "count": 0, "listening": False}


def _on_duration(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["seconds"] += secs
        _COMPILE["count"] += 1


def _listen_compiles():
    if not _COMPILE.get("listening"):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        _COMPILE["listening"] = True


class Phase:
    """``with Phase("name", report) as p: ...`` records the phase's wall
    seconds and the compile seconds/count inside it."""

    def __init__(self, name: str, report: dict):
        self.name, self.report = name, report

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = dict(_COMPILE)
        return self

    def __exit__(self, *exc):
        rec = {"seconds": time.perf_counter() - self.t0,
               "compile_seconds": _COMPILE["seconds"] - self.c0["seconds"],
               "compiles": _COMPILE["count"] - self.c0["count"]}
        self.report["phases"][self.name] = rec
        if exc[0] is None:
            emit("phase", name=self.name, **rec)
        return False


def _block(res):
    """Wait for the device work behind a Result/Report/ExecutionResult."""
    import jax
    out = getattr(res, "value", None)
    if out is None:
        out = getattr(res, "result", res)
    jax.block_until_ready(getattr(out, "data", out))
    return res


def _data(v):
    return np.asarray(v.data if hasattr(v, "data") else v)


def _test_histogram(test_wave: np.ndarray) -> np.ndarray:
    """The test patient's raw histogram (registered as ``test_hist``)."""
    hist, _ = np_bin_hist(np_haar(test_wave[None, :], LEVELS), NBINS, LEVELS)
    return hist.astype(np.float32)


def _dense_plan(query):
    from repro.core.planner import Plan
    return Plan(tuple((i, "dense_array") for i in range(len(query.nodes()))))


def _memory():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def _pallas_proof(s, q, report):
    """``tpu_custom_call`` in the lowered text of the dense ``haar``/``knn``
    ops at the served shapes, and of every fused segment the production
    plan served."""
    import jax
    from repro.core import fuseplan
    from repro.core.executor import execute_plan
    from repro.kernels import ops as kops
    cat = s.catalog
    waves = cat["waves"].obj.data
    w = execute_plan(q.inputs[0], _dense_plan(q.inputs[0]), cat).value.data
    th = cat["test_hist"].obj.data
    texts = {
        "dense haar": jax.jit(lambda x: kops.haar(x, LEVELS)).lower(waves),
        "dense knn": jax.jit(lambda a, b: kops.knn(a, b, K)).lower(w, th),
    }
    entry = s.bigdawg.plan_cache[report["analytic_sig"]]
    nodes = q.nodes()
    for seg in getattr(entry.fused, "segments", ()):
        ext = []
        for kind, src in seg.ext_sources:
            if kind == "ref":
                ext.append(cat[src].obj)
            else:
                sub = nodes[src]
                ext.append(execute_plan(sub, _dense_plan(sub), cat).value)
        args = (tuple(o.data for o in ext),
                tuple(float(o.fill) for o in ext),
                tuple(int(o.valid_count) for o in ext))
        name = "fused " + "+".join(seg.ops)
        texts[name] = fuseplan.compiled_segment(seg).lower(*args)
    proof = {name: "tpu_custom_call" in low.as_text()
             for name, low in texts.items()}
    emit("pallas", **proof)
    return proof


# -- one chip ------------------------------------------------------------------

def run_single(size: dict, seed: int) -> dict:
    """Every one-chip phase; raises ``CheckFailed`` at the first wrong
    answer.  Returns the report (phases, checks, counters, proof)."""
    import jax
    import jax.numpy as jnp
    from repro.core import DenseTensor, connect, execute_plan
    from repro.data import mimic_like_dataset

    _listen_compiles()
    report = {"phases": {}, "checks": {}, "plan_keys": []}
    n, t = size["patients"], size["samples"]
    s = connect(train_plans=2, resilient=False)
    with Phase("setup", report):
        ds = mimic_like_dataset(n + 1, t, seed)
        waves_all = ds["waveforms"].data
        host = np.asarray(waves_all)
        waves, test_wave = host[:-1], host[-1]
        s.register("waves", DenseTensor(waves_all[:-1]), engine="dense_array")
        s.register("test_hist", DenseTensor(jnp.asarray(
            _test_histogram(test_wave))), engine="dense_array")
        s.register("patients", ds["patients"], engine="columnar")
        s.register("notes", ds["notes"], engine="kv_sparse")
        del waves_all, ds
        _block(s.catalog["waves"].obj)
    emit("resident", **_memory())
    q = build_query(s)
    th = s.catalog["test_hist"].obj.data

    # per-stage references, each fed the system's previous stage
    with Phase("stages", report):
        stage = {}
        for name, sub in (("haar", q.inputs[0].inputs[0].inputs[0]),
                          ("bin_hist", q.inputs[0].inputs[0]),
                          ("tfidf", q.inputs[0])):
            stage[name] = _data(execute_plan(sub, _dense_plan(sub),
                                             s.catalog).value)
    report["checks"]["haar_max_abs_err"] = check_haar(stage["haar"], waves)
    report["checks"]["hist_counts_on_edges"] = check_hist(stage["bin_hist"],
                                                          stage["haar"])
    report["checks"]["tfidf_max_abs_err"] = check_tfidf(stage["tfidf"],
                                                        stage["bin_hist"])
    weights = stage["tfidf"]
    del stage
    margins = {}

    with Phase("analytic_training", report):
        res = _block(s.execute(q, mode="training"))
    report["analytic_sig"] = res.sig
    report["plan_keys"].append(("training", res.plan_key))
    margins["training"] = check_knn(_data(res.value), weights, th)

    with Phase("analytic_production", report):
        serves = [_block(s.execute(q, mode="production"))
                  for _ in range(PRODUCTION_SERVES)]
    for i, r in enumerate(serves):
        check(r.mode == "production", f"serve {i} ran in {r.mode} mode")
        report["plan_keys"].append(("production", r.plan_key))
        margins[f"production_{i}"] = check_knn(_data(r.value), weights, th)

    for name, plan in named_plans(q).items():
        with Phase(f"placement_{name}", report):
            r = _block(execute_plan(q, plan, s.catalog))
        margins[name] = check_knn(_data(r.value), weights, th)

    srv = s.server()
    with Phase("query_server", report):
        reps = srv.submit_many([q] * CONCURRENT_REQUESTS,
                               workers=CONCURRENT_REQUESTS)
        reps = [_block(r) for r in reps]
    for i, r in enumerate(reps):
        report["plan_keys"].append(("served", r.plan_key))
        margins[f"served_{i}"] = check_knn(_data(r.result), weights, th)
    report["checks"]["knn_min_margin"] = margins

    # (off a TPU the kernels' jnp references run: no proof to demand there,
    # and main() refuses the result)
    report["pallas"] = _pallas_proof(s, q, report)
    check(jax.default_backend() != "tpu" or all(report["pallas"].values()),
          f"not compiled Pallas: {report['pallas']}")

    # the paper's §III-C-2 cross-island query, on its own session
    cross = connect(train_plans=1, resilient=False)
    rng = np.random.default_rng(seed + 3)
    a = rng.normal(size=(size["a"], size["a"])).astype(np.float32)
    b = rng.normal(size=(size["a"], size["b_cols"])).astype(np.float32)
    with Phase("cross_island", report):
        cross.register("A", DenseTensor(jnp.asarray(a)), engine="columnar")
        cross.register("B", DenseTensor(jnp.asarray(b)), engine="dense_array")
        outs = [_block(cross.execute(CROSS_QUERY, mode=m))
                for m in ("training", "production", "production")]
    errs = []
    for r in outs:
        report["plan_keys"].append(("cross_island", r.plan_key))
        errs.append(check_matmul(_data(r.value), a, b))
    report["checks"]["matmul_max_abs_err"] = max(errs)

    counters = {}
    for tag, bd in (("analytic", s.bigdawg), ("cross_island",
                                              cross.bigdawg)):
        counters[tag] = {"fusion_fallbacks": bd.fusion_fallbacks,
                         "ivm_fallbacks": bd.ivm_fallbacks,
                         "breaker_trips": bd.breaker_trips,
                         "fused_serves": bd.fused_serves}
    counters["query_server"] = {k: srv.stats[k] for k in (
        "fusion_fallbacks", "ivm_fallbacks", "breaker_trips", "requests")}
    report["counters"] = counters
    emit("plan_keys", keys=report["plan_keys"])
    emit("counters", **counters)
    for tag, c in counters.items():
        for k in ("fusion_fallbacks", "ivm_fallbacks", "breaker_trips"):
            check(c[k] == 0, f"{tag} {k} = {c[k]}")
    emit("checks", **report["checks"])
    emit("resident", **_memory())
    return report


# -- four chips: the worker pool -------------------------------------------

def run_pool(size: dict, seed: int, chips: int) -> dict:
    """Pool path only.  The parent stays off JAX until the pool is closed
    (its workers hold the chips), then answers the same queries in-process
    on its own and compares."""
    from repro.core import DenseTensor, connect
    from repro.core.islands import array
    from repro.data.synthetic import ecg_waveforms

    report = {"phases": {}, "checks": {}}
    n, t = size["patients"], size["samples"]
    t0 = time.perf_counter()
    host, _ = ecg_waveforms(n + 1, t, seed)
    waves = host[:-1]
    th = _test_histogram(host[-1])
    hist_q = array.bin_hist(array.haar("waves", levels=LEVELS),
                            nbins=NBINS, levels=LEVELS)
    emit("phase", name="pool_data", seconds=time.perf_counter() - t0)

    t0 = time.perf_counter()
    # train_plans=1: the pool path is under test here, not plan search
    with connect(processes=chips, train_plans=1, scatter="always") as s:
        s.register("waves", DenseTensor(waves), engine="dense_array",
                   shards=chips)
        s.register("test_hist", DenseTensor(th), engine="dense_array")
        devs = s.bigdawg.devices()
        emit("phase", name="pool_start", seconds=time.perf_counter() - t0)
        emit("workers", devices=devs)
        t0 = time.perf_counter()
        pool_hist = s.execute(hist_q, mode="training")
        pool_hist_prod = s.execute(hist_q, mode="production")
        q = build_query(s)
        pool_knn = s.execute(q, mode="training")
        emit("phase", name="pool_serve", seconds=time.perf_counter() - t0)
    check(pool_hist.report.shards == chips and
          pool_hist_prod.report.shards == chips,
          f"prefix not scatter-gathered over {chips} shards")
    if device.tpu_chips():                   # (a CPU rehearsal pins none)
        check(all(d["platform"] == "tpu" for d in devs),
              f"a worker is not on a TPU: {devs}")
        check(len({d["chip"] for d in devs}) == chips,
              f"workers share a chip: {devs}")

    # the same queries in-process (the parent's first JAX work)
    _listen_compiles()
    import jax.numpy as jnp
    from repro.core.executor import execute_plan
    one = connect(train_plans=1, resilient=False)
    one.register("waves", DenseTensor(jnp.asarray(waves)),
                 engine="dense_array")
    one.register("test_hist", DenseTensor(jnp.asarray(th)),
                 engine="dense_array")
    with Phase("in_process", report):
        in_hist = _data(one.execute(hist_q, mode="training").value)
        in_knn = _data(one.execute(build_query(one), mode="training").value)
        coeffs = _data(execute_plan(hist_q.inputs[0],
                                    _dense_plan(hist_q.inputs[0]),
                                    one.catalog).value)
    got_hist = _data(pool_hist.value)
    check(np.array_equal(got_hist, _data(pool_hist_prod.value)),
          "pool histograms differ between serves")
    check(np.array_equal(got_hist, in_hist),
          "pool histograms differ from the in-process session")
    report["checks"]["haar_max_abs_err"] = check_haar(coeffs, waves)
    report["checks"]["hist_counts_on_edges"] = check_hist(got_hist, coeffs)
    weights = np_tfidf(got_hist)
    report["checks"]["knn_min_margin"] = {
        "pool": check_knn(_data(pool_knn.value), weights, th),
        "in_process": check_knn(in_knn, weights, th)}
    check(set(_data(pool_knn.value).ravel().tolist())
          == set(in_knn.ravel().tolist()),
          "pool neighbours differ from the in-process session")
    emit("checks", **report["checks"])
    return report


# -- entry point ---------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true",
                    help="rehearsal sizes (runs on the CPU backend too)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the worker-pool path on a four-chip host only")
    args = ap.parse_args(argv)
    size = SMALL if args.small else FULL
    device.enable_compile_cache()
    if args.chips > 1:
        if not args.small and device.tpu_chips() < args.chips:
            print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
                  f"chips; found {device.tpu_chips()}", file=sys.stderr)
            return 2
        run_pool(size, args.seed, args.chips)
    else:
        import jax
        if not args.small and jax.devices()[0].platform != "tpu":
            print(f"chip_smoke: no TPU (JAX found "
                  f"{jax.devices()[0].platform}); full size runs only on a "
                  f"TPU", file=sys.stderr)
            return 2
        run_single(size, args.seed)
    import jax
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: every check passed, but on {dev.platform}, not a "
              f"TPU: no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
