"""Execution engines — the polystore's heterogeneous backends.

Each engine is an *execution regime*: a native data layout plus layout-true
algorithms.  The relative strengths are real, not simulated:

  dense_array (SciDB-analogue)   O(1) metadata count; MXU-shaped matmul/Haar;
                                 distinct must scan padded storage.
  columnar (Postgres/Myria)      scan count; sort-based distinct/group/join on
                                 compacted columns; matmul only via
                                 join-aggregate over triples (the paper's
                                 166-minute Postgres anecdote).
  kv_sparse (Accumulo/Graphulo)  O(1) nnz count; segment-sum spmm; natural
                                 TF-IDF over triples (D4M associative arrays).
  stream (S-Store)               windowed aggregation via scan; ETL to arrays.

Every op: fn(attrs, *containers) -> container.  Ops that a given engine cannot
express are simply absent — the planner must cast (paper: islands have partial
engine coverage).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata

from repro.core.tables import COOMatrix, ColumnarTable, DenseTensor, StreamBuffer


# ==========================================================================
# shared math
# ==========================================================================

def haar_1d_levels(x: jnp.ndarray, levels: int) -> jnp.ndarray:
    """Multi-level Haar DWT over the last axis.  Returns [a_L, d_L, ..., d_1]
    concatenated (same length as input; length must be divisible by 2^levels)."""
    inv = 1.0 / math.sqrt(2.0)
    details = []
    a = x
    for _ in range(levels):
        e, o = a[..., 0::2], a[..., 1::2]
        details.append((e - o) * inv)
        a = (e + o) * inv
    return jnp.concatenate([a] + details[::-1], axis=-1)


def _scale_slices(T: int, levels: int):
    """[(offset, length)] per band of the haar_1d_levels output layout."""
    out = [(0, T >> levels)]
    off = T >> levels
    for l in range(levels, 0, -1):
        n = T >> l
        out.append((off, n))
        off += n
    return out


def tfidf_dense(tf: jnp.ndarray) -> jnp.ndarray:
    """tf: (docs, terms) counts -> l2-normalized tf-idf."""
    n = tf.shape[0]
    df = jnp.sum(tf > 0, axis=0)
    idf = jnp.log(n / (1.0 + df.astype(jnp.float32))) + 1.0
    w = tf.astype(jnp.float32) * idf[None, :]
    norm = jnp.linalg.norm(w, axis=1, keepdims=True)
    return w / jnp.maximum(norm, 1e-9)


# ==========================================================================
# dense_array engine
# ==========================================================================

def _da_count(attrs, d: DenseTensor):
    # SciDB-style: element count is container metadata — O(1)
    return DenseTensor(jnp.asarray(d.valid_count, jnp.int32), valid_count=1)


def _da_distinct(attrs, d: DenseTensor):
    # must scan the full (padded) dense storage — fill values included in the
    # sort, exactly the cost a real array store pays on sparse data
    flat = jnp.sort(d.data.ravel())
    neq = jnp.concatenate([jnp.array([True]), flat[1:] != flat[:-1]])
    return DenseTensor(jnp.sum(neq).astype(jnp.int32), valid_count=1)


def _da_matmul(attrs, a: DenseTensor, b: DenseTensor):
    # HIGHEST: the TPU's default precision would round f32 operands to
    # bfloat16 — an approximate answer where every other engine is exact
    return DenseTensor(jnp.dot(a.data, b.data,
                               precision=jax.lax.Precision.HIGHEST))


def _da_select(attrs, d: DenseTensor):
    lo, hi = attrs.get("lo", -np.inf), attrs.get("hi", np.inf)
    m = (d.data >= lo) & (d.data <= hi)
    return DenseTensor(jnp.where(m, d.data, d.fill),
                       valid_count=int(jnp.sum(m)))


def _da_haar(attrs, d: DenseTensor):
    # TPU hot spot — served by kernels/haar.py on real hardware
    from repro.kernels import ops as kops
    return DenseTensor(kops.haar(d.data, attrs["levels"]))


@partial(jax.jit, static_argnames=("nbins", "levels"))
def dense_bin_hist(x, lo, span, scale, *, nbins: int, levels: int):
    """(N, T) Haar coefficients -> (N, (levels + 1) * nbins) float32 counts,
    band by band; the bin of ``x`` is ``clip(int32((x - lo) / span * scale),
    0, nbins - 1)``.  Each band's bins are compared with every bin and
    summed in one program, which XLA fuses into the sum: no (N, band, nbins)
    buffer is stored.  ``lo``, ``span`` and ``scale`` are operands, as they
    are to the op-by-op formula: a constant divisor would let XLA multiply
    by its reciprocal instead, which rounds differently at some bin edges.

    Every device op of the program carries the frontend attribute
    ``bigdawg_op="dense_bin_hist"``: a TPU trace names an op by its HLO
    text, and the attribute is what finds these ops there."""
    with set_xla_metadata(bigdawg_op="dense_bin_hist"):
        outs = []
        for off, ln in _scale_slices(x.shape[1], levels):
            idx = jnp.clip(((x[:, off:off + ln] - lo) / span * scale)
                           .astype(jnp.int32), 0, nbins - 1)
            outs.append(jnp.sum(idx[:, :, None] == jnp.arange(nbins), axis=1,
                                dtype=jnp.float32))
        return jnp.concatenate(outs, axis=1)


def _da_bin_hist(attrs, d: DenseTensor):
    """Per-band histograms of Haar coefficients, one compiled call: the
    coefficients are read once and no one-hot is materialised."""
    nbins, levels = attrs["nbins"], attrs["levels"]
    lo, hi = attrs.get("lo", -3.0), attrs.get("hi", 3.0)
    return DenseTensor(dense_bin_hist(d.data, lo, hi - lo, nbins,
                                      nbins=nbins, levels=levels))


def _da_tfidf(attrs, d: DenseTensor):
    return DenseTensor(tfidf_dense(d.data))


def _da_knn(attrs, train: DenseTensor, test: DenseTensor):
    """Cosine-distance kNN via one GEMM + top-k (kernels/knn.py on TPU)."""
    from repro.kernels import ops as kops
    idx, score = kops.knn(train.data, jnp.atleast_2d(test.data), attrs["k"])
    return DenseTensor(idx)


def _da_add(attrs, a, b):
    return DenseTensor(a.data + b.data)


def _da_concat(attrs, a: DenseTensor, b: DenseTensor):
    """Row concatenation (leading axis).  ``valid_count`` adds — exact for
    unpadded operands, and for padded ones the per-operand counts are still
    the only row-attributable accounting available."""
    return DenseTensor(jnp.concatenate([a.data, b.data], axis=0),
                       valid_count=a.valid_count + b.valid_count,
                       fill=a.fill)


def _da_scale(attrs, a):
    return DenseTensor(a.data * attrs["factor"])


def _da_transpose(attrs, a):
    return DenseTensor(a.data.T)


# ==========================================================================
# columnar engine
# ==========================================================================

def _col_count(attrs, t: ColumnarTable):
    # full validity scan — Postgres-style COUNT(*)
    return DenseTensor(jnp.sum(t.valid).astype(jnp.int32), valid_count=1)


def _col_distinct(attrs, t: ColumnarTable):
    col = attrs.get("column", "value")
    v = t.columns[col]
    sentinel = jnp.asarray(np.inf, v.dtype) if jnp.issubdtype(v.dtype, jnp.floating) \
        else jnp.iinfo(v.dtype).max
    vv = jnp.where(t.valid, v, sentinel)
    s = jnp.sort(vv)
    neq = jnp.concatenate([jnp.array([True]), s[1:] != s[:-1]])
    valid_sorted = jnp.sort(t.valid)[::-1]
    return DenseTensor(jnp.sum(neq & valid_sorted).astype(jnp.int32),
                       valid_count=1)


def _col_select(attrs, t: ColumnarTable):
    col, lo, hi = attrs["column"], attrs.get("lo", -np.inf), attrs.get("hi", np.inf)
    v = t.columns[col]
    m = t.valid & (v >= lo) & (v <= hi)
    return ColumnarTable(dict(t.columns), valid=m)


def _col_project(attrs, t: ColumnarTable):
    return ColumnarTable({c: t.columns[c] for c in attrs["columns"]},
                         valid=t.valid)


def _col_groupby_sum(attrs, t: ColumnarTable):
    key, val = attrs["key"], attrs["value"]
    nseg = attrs["num_groups"]
    k = jnp.where(t.valid, t.columns[key], nseg)         # invalid -> overflow seg
    s = jax.ops.segment_sum(t.columns[val], k, num_segments=nseg + 1)[:-1]
    return ColumnarTable({"key": jnp.arange(nseg, dtype=jnp.int32), "sum": s})


def _col_sort(attrs, t: ColumnarTable):
    """ORDER BY ``attrs["by"]`` (stable).  Output is COMPACTED — invalid rows
    are dropped, not carried — which is what makes the scatter–gather merge
    for this op a pure k-way ordered merge of per-shard runs.  Columns stay
    numpy for the same host-pool reasons as the join."""
    by = attrs["by"]
    valid = np.asarray(t.valid)
    cols = {c: np.asarray(v) for c, v in t.columns.items()}
    if not valid.all():
        cols = {c: v[valid] for c, v in cols.items()}
    order = np.argsort(cols[by], kind="stable")
    return ColumnarTable({c: v[order] for c, v in cols.items()})


def _col_join(attrs, a: ColumnarTable, b: ColumnarTable):
    """Sort-merge equi-join (eager; dynamic output size).

    The output columns stay NUMPY: argsort/searchsorted/fancy-indexing are
    host work that releases the GIL (what makes joins overlap on the host
    pool), and wrapping the result in ``jnp.asarray`` here would serialize
    every worker on the XLA transfer lock.  A downstream device consumer
    (segment_sum in matmul/knn, a dense cast) pulls the columns over when it
    actually needs them."""
    ka, kb = attrs["left_on"], attrs["right_on"]
    av = np.asarray(a.valid); bv = np.asarray(b.valid)

    def live(cols, mask):
        # skip the boolean gather when nothing is masked out (the common
        # catalog-table case): an all-true fancy index would copy every
        # column — pure memory-bandwidth burn that scales terribly across
        # concurrent requests
        if mask.all():
            return {c: np.asarray(v) for c, v in cols.items()}
        return {c: np.asarray(v)[mask] for c, v in cols.items()}

    an = live(a.columns, av)
    bn = live(b.columns, bv)
    order = np.argsort(bn[kb], kind="stable")
    bk = bn[kb][order]
    left = np.searchsorted(bk, an[ka], side="left")
    right = np.searchsorted(bk, an[ka], side="right")
    counts = right - left
    ai = np.repeat(np.arange(an[ka].shape[0]), counts)
    offs = (left.astype(np.int64).repeat(counts)
            + _ranges_from_counts(counts))
    bi = order[offs]
    cols = {("l_" + c if c in bn else c): v[ai]
            for c, v in an.items()}
    cols.update({("r_" + c if ("l_" + c) in cols or c in an else c):
                 v[bi] for c, v in bn.items()})
    return ColumnarTable(cols)


def _ranges_from_counts(counts):
    total = int(counts.sum())
    out = np.ones(total, np.int64)
    if total == 0:
        return out
    starts = np.cumsum(counts)[:-1]
    out[0] = 0
    # zero counts make `starts` repeat an index; plain fancy-index -= keeps
    # only the last repeat's update, so unmatched rows corrupt every range
    # after them — subtract.at accumulates all of them.  Trailing zero
    # counts land a start AT ``total``: past every live range, droppable
    live = starts < total
    np.subtract.at(out, starts[live], counts[:-1][live])
    return np.cumsum(out)


def _col_matmul(attrs, a: ColumnarTable, b: ColumnarTable):
    """Relational matrix multiply: join A.j == B.i, multiply, group by (A.i,
    B.j) — the paper's Postgres-in-166-minutes formulation."""
    j = _col_join({"left_on": "j", "right_on": "i"} | {},
                  ColumnarTable({"i": a.columns["i"], "j": a.columns["j"],
                                 "value": a.columns["value"]}, a.valid),
                  ColumnarTable({"i": b.columns["i"], "j": b.columns["j"],
                                 "value": b.columns["value"]}, b.valid))
    prod = j.columns["l_value"] * j.columns["r_value"]
    n = int(jnp.max(j.columns["l_i"])) + 1 if j.nrows else 0
    m = int(jnp.max(j.columns["r_j"])) + 1 if j.nrows else 0
    key = j.columns["l_i"].astype(jnp.int32) * m + j.columns["r_j"]
    s = jax.ops.segment_sum(prod, key, num_segments=n * m)
    return ColumnarTable({
        "i": (jnp.arange(n * m) // m).astype(jnp.int32),
        "j": (jnp.arange(n * m) % m).astype(jnp.int32),
        "value": s})


def _col_haar(attrs, t: ColumnarTable):
    """Haar in the relational engine: ORDER BY (i, j), restructure to rows,
    transform, flatten back — the ordering/restructure cost is the honest
    price a row store pays for array math (paper Fig. 5, SciDB side)."""
    order = jnp.lexsort((t.columns["j"], t.columns["i"]))
    v = t.columns["value"][order]
    n = int(jnp.max(t.columns["i"])) + 1
    T = int(jnp.max(t.columns["j"])) + 1
    mat = v.reshape(n, T)
    out = haar_1d_levels(mat, attrs["levels"])
    return ColumnarTable({"i": t.columns["i"][order], "j": t.columns["j"][order],
                          "value": out.ravel()})


def _col_bin_hist(attrs, t: ColumnarTable):
    """Sort/segment histogram — natural in a column store."""
    nbins, levels = attrs["nbins"], attrs["levels"]
    lo, hi = attrs.get("lo", -3.0), attrs.get("hi", 3.0)
    i, jj, v = t.columns["i"], t.columns["j"], t.columns["value"]
    n = int(jnp.max(i)) + 1
    T = int(jnp.max(jj)) + 1
    slices = _scale_slices(T, levels)
    starts = jnp.asarray([s for s, _ in slices] + [T])
    scale_of_j = jnp.searchsorted(starts, jj, side="right") - 1
    b = jnp.clip(((v - lo) / (hi - lo) * nbins).astype(jnp.int32), 0, nbins - 1)
    nscales = len(slices)
    key = (i.astype(jnp.int32) * nscales + scale_of_j) * nbins + b
    hist = jax.ops.segment_sum(jnp.ones_like(v, jnp.float32), key,
                               num_segments=n * nscales * nbins)
    hh = hist.reshape(n, nscales * nbins)
    ii, bb = jnp.meshgrid(jnp.arange(n), jnp.arange(nscales * nbins),
                          indexing="ij")
    return ColumnarTable({"i": ii.ravel().astype(jnp.int32),
                          "j": bb.ravel().astype(jnp.int32),
                          "value": hh.ravel()})


def _col_tfidf(attrs, t: ColumnarTable):
    """TF-IDF over (i=doc, j=term, value=tf) triples via segment ops."""
    i, jj, v = t.columns["i"], t.columns["j"], t.columns["value"]
    n = int(jnp.max(i)) + 1
    V = int(jnp.max(jj)) + 1
    df = jax.ops.segment_sum((v > 0).astype(jnp.float32), jj, num_segments=V)
    idf = jnp.log(n / (1.0 + df)) + 1.0
    w = v * idf[jj]
    norm2 = jax.ops.segment_sum(w * w, i, num_segments=n)
    w = w / jnp.sqrt(jnp.maximum(norm2[i], 1e-18))
    return ColumnarTable({"i": i, "j": jj, "value": w})


def _col_knn(attrs, train: ColumnarTable, test: ColumnarTable):
    """kNN as join-aggregate: join train and test on the term column, multiply,
    group by train doc."""
    j = _col_join({"left_on": "j", "right_on": "j"},
                  train, ColumnarTable({"j": test.columns["j"],
                                        "value": test.columns["value"]},
                                       test.valid))
    prod = j.columns["l_value"] * j.columns["r_value"]
    n = int(jnp.max(j.columns["i"])) + 1
    scores = jax.ops.segment_sum(prod, j.columns["i"], num_segments=n)
    _, idx = jax.lax.top_k(scores, attrs["k"])
    return DenseTensor(idx[None, :])


# ==========================================================================
# kv_sparse engine (Accumulo / Graphulo / D4M)
# ==========================================================================

def _kv_count(attrs, m: COOMatrix):
    return DenseTensor(jnp.asarray(m.nnz, jnp.int32), valid_count=1)


def _kv_distinct(attrs, m: COOMatrix):
    s = jnp.sort(m.vals)
    neq = jnp.concatenate([jnp.array([True]), s[1:] != s[:-1]])
    return DenseTensor(jnp.sum(neq).astype(jnp.int32), valid_count=1)


def _kv_spmm(attrs, m: COOMatrix, d: DenseTensor):
    """Graphulo-style server-side sparse matmul: segment-sum over triples."""
    contrib = m.vals[:, None] * d.data[m.cols]
    out = jax.ops.segment_sum(contrib, m.rows, num_segments=m.shape[0])
    return DenseTensor(out)


def _kv_tfidf(attrs, m: COOMatrix):
    n, V = m.shape
    df = jax.ops.segment_sum((m.vals > 0).astype(jnp.float32), m.cols,
                             num_segments=V)
    idf = jnp.log(n / (1.0 + df)) + 1.0
    w = m.vals * idf[m.cols]
    norm2 = jax.ops.segment_sum(w * w, m.rows, num_segments=n)
    w = w / jnp.sqrt(jnp.maximum(norm2[m.rows], 1e-18))
    return COOMatrix(m.rows, m.cols, w, m.shape)


def _kv_knn(attrs, train: COOMatrix, test):
    if isinstance(test, COOMatrix):         # migrator homed the test vector
        dense = jnp.zeros(test.shape[1], jnp.float32).at[test.cols].set(
            test.vals.astype(jnp.float32))
        q = dense
    else:
        q = test.data.ravel()
    contrib = train.vals * q[train.cols]
    scores = jax.ops.segment_sum(contrib, train.rows,
                                 num_segments=train.shape[0])
    _, idx = jax.lax.top_k(scores, attrs["k"])
    return DenseTensor(idx[None, :])


def _kv_degree(attrs, m: COOMatrix):
    axis = attrs.get("axis", 0)
    seg = m.rows if axis == 0 else m.cols
    n = m.shape[axis]
    return DenseTensor(jax.ops.segment_sum(jnp.ones_like(m.vals), seg,
                                           num_segments=n))


# ==========================================================================
# stream engine (S-Store)
# ==========================================================================

def _st_window_agg(attrs, s: StreamBuffer):
    fn = {"mean": jnp.mean, "max": jnp.max, "min": jnp.min}[attrs.get("fn", "mean")]
    return DenseTensor(fn(s.data, axis=1))


def _st_haar(attrs, s: StreamBuffer):
    return StreamBuffer(haar_1d_levels(s.data, attrs["levels"]), s.t0)


def _st_to_array(attrs, s: StreamBuffer):
    return DenseTensor(s.data.reshape(-1, s.data.shape[-1]))


def _st_ingest(attrs, s: StreamBuffer, d: DenseTensor):
    """Append new windows (ETL path of the paper's streaming application)."""
    new = d.data.reshape((-1,) + s.data.shape[1:])
    return StreamBuffer(jnp.concatenate([s.data, new], axis=0), s.t0)


# ==========================================================================
# registry
# ==========================================================================

class Engine:
    def __init__(self, name: str, kind: str, ops: Dict[str, Callable]):
        self.name = name
        self.kind = kind          # native container kind
        self.ops = ops

    def supports(self, op: str) -> bool:
        return op in self.ops

    def run(self, op: str, attrs, *inputs):
        return self.ops[op](attrs, *inputs)

    def __repr__(self):
        return f"Engine({self.name})"


ENGINES: Dict[str, Engine] = {
    "dense_array": Engine("dense_array", "dense", {
        "count": _da_count, "distinct": _da_distinct, "matmul": _da_matmul,
        "select": _da_select, "haar": _da_haar, "bin_hist": _da_bin_hist,
        "tfidf": _da_tfidf, "knn": _da_knn, "add": _da_add,
        "scale": _da_scale, "transpose": _da_transpose,
        "concat": _da_concat,
    }),
    "columnar": Engine("columnar", "columnar", {
        "count": _col_count, "distinct": _col_distinct, "select": _col_select,
        "project": _col_project, "groupby_sum": _col_groupby_sum,
        "sort": _col_sort,
        "join": _col_join, "matmul": _col_matmul, "haar": _col_haar,
        "bin_hist": _col_bin_hist, "tfidf": _col_tfidf, "knn": _col_knn,
    }),
    "kv_sparse": Engine("kv_sparse", "coo", {
        "count": _kv_count, "distinct": _kv_distinct, "spmm": _kv_spmm,
        "tfidf": _kv_tfidf, "knn": _kv_knn, "degree": _kv_degree,
    }),
    "stream": Engine("stream", "stream", {
        "window_agg": _st_window_agg, "haar": _st_haar,
        "to_array": _st_to_array, "ingest": _st_ingest,
    }),
}
