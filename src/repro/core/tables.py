"""Polystore data containers — one native format per engine family.

These mirror the paper's data models: SciDB arrays -> DenseTensor, relational
rows -> ColumnarTable, Accumulo/D4M associative arrays -> COOMatrix, S-Store
windows -> StreamBuffer.  ``nbytes``/``describe`` feed the cast cost model.

Triple-format containers (ColumnarTable, COOMatrix) accept **either** jnp or
numpy arrays for their columns/triples.  Eagerly-computed intermediates —
sort-merge join output, dense->triple casts — stay numpy until a dense
consumer actually needs the device: wrapping them in ``jnp.asarray`` at
creation would serialize every host-pool worker on the XLA transfer lock for
data the next op may never touch on-device (see ``device_ready`` for the
explicit homing used on long-lived catalog objects).
Row-range sharding (the multi-process scatter–gather substrate) also lives
here: ``shard_rows`` splits any container into N contiguous row-range parts,
and the three merge primitives — ``concat_shards`` (row-wise ops),
``sum_shards`` (decomposable aggregates: count, groupby_sum), and
``kmerge_shards`` (k-way ordered merge of per-shard sorted tables) —
reassemble per-shard results.  The merge helpers are deliberately
numpy-only: they run in the MASTER process, which must never initialize the
XLA backend (workers own the device; see ``core/procpool.py``).

The streaming/IVM substrate (``core/deltaplan.py``) builds on the same
row-range algebra: a table that grew by appended rows is exactly a 2-shard
decomposition ``[old prefix, appended suffix]``, so ``append_rows`` /
``suffix_rows`` here are the base+delta halves of ``shard_rows`` /
``concat_shards``.  Both follow their input's residency (numpy in, numpy
out) so the procpool master can maintain its catalog mirror without
touching the XLA runtime.  ``container_to_jsonable`` /
``container_from_jsonable`` round-trip small containers through JSON — the
materialized-view persistence format that rides beside the plan cache.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


@dataclass
class DenseTensor:
    """Array-engine native: a dense (possibly padded) tensor.

    ``valid_count`` is container metadata (SciDB-style): count() is O(1) here
    but a full scan in the columnar engine — the Fig.1 crossover.
    """
    data: jnp.ndarray
    valid_count: int = -1
    fill: float = 0.0

    def __post_init__(self):
        if self.valid_count < 0:
            self.valid_count = int(np.prod(self.data.shape))

    @property
    def nbytes(self) -> int:
        return self.data.size * self.data.dtype.itemsize

    kind = "dense"


@dataclass
class ColumnarTable:
    """Relational-engine native: named columns + validity mask (lazy deletes)."""
    columns: Dict[str, jnp.ndarray]
    valid: jnp.ndarray = None    # (N,) bool

    def __post_init__(self):
        n = self.nrows
        if self.valid is None:
            # follow the columns' residency: numpy columns get a numpy mask
            # (building a device mask for a host-side intermediate would
            # trigger exactly the transfer this layout avoids)
            first = next(iter(self.columns.values()))
            ones = np.ones if isinstance(first, np.ndarray) else jnp.ones
            self.valid = ones((n,), bool)

    @property
    def nrows(self) -> int:
        return next(iter(self.columns.values())).shape[0]

    @property
    def nbytes(self) -> int:
        return sum(c.size * c.dtype.itemsize for c in self.columns.values())

    kind = "columnar"


@dataclass
class COOMatrix:
    """KV/associative-array native (D4M style): (row, col, val) triples."""
    rows: jnp.ndarray
    cols: jnp.ndarray
    vals: jnp.ndarray
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return self.rows.shape[0]

    @property
    def nbytes(self) -> int:
        return (self.rows.size * self.rows.dtype.itemsize
                + self.cols.size * self.cols.dtype.itemsize
                + self.vals.size * self.vals.dtype.itemsize)

    kind = "coo"


@dataclass
class StreamBuffer:
    """Stream-engine native: window-major ring buffer of samples."""
    data: jnp.ndarray            # (n_windows, window_len, ...) newest last
    t0: int = 0                  # timestamp of the first window

    @property
    def nbytes(self) -> int:
        return self.data.size * self.data.dtype.itemsize

    kind = "stream"


FORMATS = {"dense": DenseTensor, "columnar": ColumnarTable, "coo": COOMatrix,
           "stream": StreamBuffer}


def device_ready(obj):
    """Home a container's array leaves on the device (``jnp.asarray``).

    For LONG-LIVED objects — catalog registrations — that will be consumed
    by device ops many times: paying the transfer once at registration beats
    re-transferring on every query.  Eager intermediates deliberately skip
    this (see module docstring)."""
    if isinstance(obj, ColumnarTable):
        return ColumnarTable({c: jnp.asarray(v)
                              for c, v in obj.columns.items()},
                             valid=jnp.asarray(obj.valid))
    if isinstance(obj, COOMatrix):
        return COOMatrix(jnp.asarray(obj.rows), jnp.asarray(obj.cols),
                         jnp.asarray(obj.vals), obj.shape)
    if isinstance(obj, DenseTensor):
        return DenseTensor(jnp.asarray(obj.data),
                           valid_count=obj.valid_count, fill=obj.fill)
    if isinstance(obj, StreamBuffer):
        return StreamBuffer(jnp.asarray(obj.data), obj.t0)
    return obj


def host_nbytes(obj) -> int:
    """Bytes of a container's numpy (host-resident) array leaves: what a
    device op has to copy to the device before it can run."""
    if isinstance(obj, ColumnarTable):
        leaves = [*obj.columns.values(), obj.valid]
    else:
        leaves = getattr(obj, "__dict__", {}).values()
    return sum(x.nbytes for x in leaves if isinstance(x, np.ndarray))


def host_copy(obj):
    """Numpy-leafed clone of a container — what the procpool master pickles
    over the worker pipe (device arrays must not cross a process boundary,
    and the master side stays off the XLA runtime entirely)."""
    if isinstance(obj, ColumnarTable):
        return ColumnarTable({c: np.asarray(v) for c, v in obj.columns.items()},
                             valid=np.asarray(obj.valid))
    if isinstance(obj, COOMatrix):
        return COOMatrix(np.asarray(obj.rows), np.asarray(obj.cols),
                         np.asarray(obj.vals), tuple(obj.shape))
    if isinstance(obj, DenseTensor):
        return DenseTensor(np.asarray(obj.data),
                           valid_count=obj.valid_count, fill=obj.fill)
    if isinstance(obj, StreamBuffer):
        return StreamBuffer(np.asarray(obj.data), obj.t0)
    return obj


# -- row-range sharding -------------------------------------------------------

def shard_bounds(nrows: int, n_shards: int) -> List[Tuple[int, int]]:
    """N contiguous ``[lo, hi)`` row ranges covering ``nrows`` (remainder
    spread over the leading shards, every shard non-degenerate when
    ``nrows >= n_shards``)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    base, rem = divmod(nrows, n_shards)
    bounds, lo = [], 0
    for i in range(n_shards):
        hi = lo + base + (1 if i < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def shard_rows(obj, n_shards: int) -> list:
    """Split a container into ``n_shards`` contiguous row-range parts.

    Dense tensors and columnar tables shard on the leading axis, COO on the
    row coordinate (rows re-based to each shard's origin), streams on the
    window axis.  Concatenating the parts back (``concat_shards``) is the
    identity.
    """
    if isinstance(obj, DenseTensor):
        a = np.asarray(obj.data)
        if a.ndim < 1:
            raise ValueError("cannot row-shard a 0-d tensor")
        if obj.valid_count not in (-1, a.size):
            # a padded tensor's valid elements are not row-attributable, so
            # per-shard counts could not reassemble to the true total
            raise ValueError("cannot row-shard a padded DenseTensor")
        return [DenseTensor(a[lo:hi], fill=obj.fill)
                for lo, hi in shard_bounds(a.shape[0], n_shards)]
    if isinstance(obj, ColumnarTable):
        cols = {c: np.asarray(v) for c, v in obj.columns.items()}
        valid = np.asarray(obj.valid)
        return [ColumnarTable({c: v[lo:hi] for c, v in cols.items()},
                              valid=valid[lo:hi])
                for lo, hi in shard_bounds(obj.nrows, n_shards)]
    if isinstance(obj, COOMatrix):
        rows = np.asarray(obj.rows)
        cols = np.asarray(obj.cols)
        vals = np.asarray(obj.vals)
        parts = []
        for lo, hi in shard_bounds(obj.shape[0], n_shards):
            m = (rows >= lo) & (rows < hi)
            parts.append(COOMatrix((rows[m] - lo).astype(rows.dtype),
                                   cols[m], vals[m],
                                   (hi - lo, obj.shape[1])))
        return parts
    if isinstance(obj, StreamBuffer):
        a = np.asarray(obj.data)
        return [StreamBuffer(a[lo:hi], t0=obj.t0 + lo)
                for lo, hi in shard_bounds(a.shape[0], n_shards)]
    raise TypeError(f"cannot shard {type(obj).__name__}")


# -- shard merges -------------------------------------------------------------

def concat_shards(parts: Sequence):
    """Reassemble row-wise per-shard results: row concatenation in shard
    order (the inverse of ``shard_rows`` for every row-preserving op)."""
    if not parts:
        raise ValueError("no shard results to merge")
    first = parts[0]
    if isinstance(first, DenseTensor):
        data = np.concatenate([np.asarray(p.data) for p in parts], axis=0)
        vc = sum(p.valid_count for p in parts)
        return DenseTensor(data, valid_count=vc, fill=first.fill)
    if isinstance(first, ColumnarTable):
        return ColumnarTable(
            {c: np.concatenate([np.asarray(p.columns[c]) for p in parts])
             for c in first.columns},
            valid=np.concatenate([np.asarray(p.valid) for p in parts]))
    if isinstance(first, COOMatrix):
        rows, off = [], 0
        for p in parts:
            rows.append(np.asarray(p.rows) + off)
            off += p.shape[0]
        return COOMatrix(np.concatenate(rows).astype(np.asarray(first.rows).dtype),
                         np.concatenate([np.asarray(p.cols) for p in parts]),
                         np.concatenate([np.asarray(p.vals) for p in parts]),
                         (off, max(p.shape[1] for p in parts)))
    if isinstance(first, StreamBuffer):
        return StreamBuffer(
            np.concatenate([np.asarray(p.data) for p in parts], axis=0),
            t0=first.t0)
    raise TypeError(f"cannot concat-merge {type(first).__name__}")


def sum_shards(parts: Sequence):
    """Merge decomposable aggregates: element-wise sum over aligned shard
    results.  Covers ``count`` (0-d DenseTensor per shard -> grand total) and
    ``groupby_sum`` (every shard emits the full aligned key range
    ``0..num_groups``, so group partial sums add position-wise)."""
    if not parts:
        raise ValueError("no shard results to merge")
    first = parts[0]
    if isinstance(first, DenseTensor):
        data = np.asarray(parts[0].data)
        for p in parts[1:]:
            data = data + np.asarray(p.data)
        return DenseTensor(data, valid_count=first.valid_count,
                           fill=first.fill)
    if isinstance(first, ColumnarTable):
        key = np.asarray(first.columns["key"])
        for p in parts[1:]:
            if not np.array_equal(np.asarray(p.columns["key"]), key):
                raise ValueError("sum-merge requires aligned group keys")
        out = {"key": key}
        for c in first.columns:
            if c == "key":
                continue
            acc = np.asarray(first.columns[c])
            for p in parts[1:]:
                acc = acc + np.asarray(p.columns[c])
            out[c] = acc
        return ColumnarTable(out)
    raise TypeError(f"cannot sum-merge {type(first).__name__}")


def kmerge_shards(parts: Sequence, by: str):
    """K-way ordered merge of per-shard SORTED columnar tables on column
    ``by`` (classic heap merge: O(total rows * log k)).  Invalid rows are
    compacted away first; ties preserve shard order (stable)."""
    if not parts:
        raise ValueError("no shard results to merge")
    compact = []
    for p in parts:
        valid = np.asarray(p.valid)
        cols = {c: np.asarray(v) for c, v in p.columns.items()}
        if not valid.all():
            cols = {c: v[valid] for c, v in cols.items()}
        compact.append(cols)
    names = list(compact[0])
    offsets = np.cumsum([0] + [c[names[0]].shape[0] for c in compact])
    def stream(cols, si):
        # bound per shard (a bare genexp in the comprehension would
        # late-bind si/cols to the last shard)
        key = cols[by]
        return ((key[i], si, offsets[si] + i) for i in range(key.shape[0]))

    streams = [stream(cols, si) for si, cols in enumerate(compact)]
    order = np.fromiter((flat for _, _, flat in heapq.merge(*streams)),
                        dtype=np.int64)
    merged = {c: np.concatenate([cols[c] for cols in compact])[order]
              for c in names}
    return ColumnarTable(merged)


# -- streaming append / delta slicing ----------------------------------------

def _xp_of(a):
    """The array module matching ``a``'s residency: numpy leaves stay numpy
    (procpool-master safe), device leaves stay on the device."""
    return np if isinstance(a, np.ndarray) else jnp


def leading_rows(obj) -> int:
    """Leading-dimension row count of a container — the quantity appends
    grow and the materialized-view freshness stamps record.  Raises
    ``TypeError`` for containers with no row dimension (0-d tensors)."""
    if isinstance(obj, ColumnarTable):
        return obj.nrows
    if isinstance(obj, COOMatrix):
        return int(obj.shape[0])
    data = getattr(obj, "data", None)
    if data is not None and getattr(data, "ndim", 0) >= 1:
        return int(data.shape[0])
    raise TypeError(f"no row dimension on {type(obj).__name__}")


def append_rows(base, delta):
    """``base`` grown by ``delta``'s rows along the leading dimension — the
    STREAM island's append semantics.  The result's old-row prefix is
    bit-identical to ``base`` (``suffix_rows(result, leading_rows(base)) ==
    delta``), which is what lets the IVM path reconstruct the pending delta
    from the current table without keeping an append log.  Containers must
    be the same kind with matching trailing geometry; padded dense tensors
    are refused for the same reason ``shard_rows`` refuses them (their
    valid elements are not row-attributable)."""
    if type(base) is not type(delta):
        raise TypeError(f"cannot append {type(delta).__name__} rows to "
                        f"{type(base).__name__}")
    if isinstance(base, DenseTensor):
        a, d = base.data, delta.data
        if getattr(a, "ndim", 0) < 1:
            raise ValueError("cannot append rows to a 0-d tensor")
        if a.shape[1:] != d.shape[1:]:
            raise ValueError(f"append shape mismatch: base rows are "
                             f"{a.shape[1:]}, delta rows are {d.shape[1:]}")
        for t in (base, delta):
            if t.valid_count not in (-1, int(np.prod(t.data.shape))):
                raise ValueError("cannot append to/with a padded DenseTensor")
        xp = _xp_of(a)
        return DenseTensor(xp.concatenate([a, xp.asarray(d)], axis=0),
                           fill=base.fill)
    if isinstance(base, ColumnarTable):
        if set(base.columns) != set(delta.columns):
            raise ValueError(f"append column mismatch: "
                             f"{sorted(base.columns)} vs "
                             f"{sorted(delta.columns)}")
        first = next(iter(base.columns.values()))
        xp = _xp_of(first)
        cols = {c: xp.concatenate([v, xp.asarray(delta.columns[c])])
                for c, v in base.columns.items()}
        valid = xp.concatenate([xp.asarray(base.valid),
                                xp.asarray(delta.valid)])
        return ColumnarTable(cols, valid=valid)
    if isinstance(base, COOMatrix):
        xp = _xp_of(base.rows)
        off = int(base.shape[0])
        rows = xp.concatenate([base.rows,
                               (xp.asarray(delta.rows) + off).astype(
                                   base.rows.dtype)])
        return COOMatrix(rows,
                         xp.concatenate([base.cols, xp.asarray(delta.cols)]),
                         xp.concatenate([base.vals, xp.asarray(delta.vals)]),
                         (off + int(delta.shape[0]),
                          max(int(base.shape[1]), int(delta.shape[1]))))
    if isinstance(base, StreamBuffer):
        if base.data.shape[1:] != delta.data.shape[1:]:
            raise ValueError("append window-shape mismatch")
        xp = _xp_of(base.data)
        return StreamBuffer(xp.concatenate([base.data,
                                            xp.asarray(delta.data)], axis=0),
                            t0=base.t0)
    raise TypeError(f"cannot append rows to {type(base).__name__}")


def suffix_rows(obj, start: int):
    """Rows ``[start:]`` of a container as a same-kind container — the
    pending delta of a streaming table whose materialized view was taken at
    ``start`` rows (the inverse of ``append_rows``)."""
    n = leading_rows(obj)
    if not 0 <= start <= n:
        raise ValueError(f"suffix start {start} outside [0, {n}]")
    if isinstance(obj, DenseTensor):
        if obj.valid_count not in (-1, int(np.prod(obj.data.shape))):
            raise ValueError("cannot row-slice a padded DenseTensor")
        return DenseTensor(obj.data[start:], fill=obj.fill)
    if isinstance(obj, ColumnarTable):
        return ColumnarTable({c: v[start:] for c, v in obj.columns.items()},
                             valid=obj.valid[start:])
    if isinstance(obj, COOMatrix):
        xp = _xp_of(obj.rows)
        m = obj.rows >= start
        return COOMatrix((obj.rows[m] - start).astype(obj.rows.dtype),
                         obj.cols[m], obj.vals[m],
                         (n - start, int(obj.shape[1])))
    if isinstance(obj, StreamBuffer):
        return StreamBuffer(obj.data[start:], t0=obj.t0 + start)
    raise TypeError(f"cannot row-slice {type(obj).__name__}")


# -- JSON round-trip (materialized-view persistence) --------------------------

def _arr_to_json(a) -> Dict:
    a = np.asarray(a)
    return {"dtype": str(a.dtype), "shape": list(a.shape),
            "data": a.ravel().tolist()}


def _arr_from_json(blob) -> np.ndarray:
    return np.asarray(blob["data"], dtype=np.dtype(blob["dtype"])).reshape(
        tuple(blob["shape"]))


def container_to_jsonable(obj):
    """A pure-JSON encoding of a container (numpy-leafed values; call
    ``host_copy`` first for device objects), or ``None`` for types this
    codec does not cover.  Sized for SMALL payloads — materialized views
    under the persistence cap — not as a general serialization format."""
    if isinstance(obj, DenseTensor):
        return {"kind": "dense", "array": _arr_to_json(obj.data),
                "valid_count": int(obj.valid_count), "fill": float(obj.fill)}
    if isinstance(obj, ColumnarTable):
        return {"kind": "columnar",
                "columns": {c: _arr_to_json(v)
                            for c, v in obj.columns.items()},
                "valid": np.asarray(obj.valid).tolist()}
    if isinstance(obj, COOMatrix):
        return {"kind": "coo", "rows": _arr_to_json(obj.rows),
                "cols": _arr_to_json(obj.cols),
                "vals": _arr_to_json(obj.vals),
                "shape": [int(obj.shape[0]), int(obj.shape[1])]}
    if isinstance(obj, StreamBuffer):
        return {"kind": "stream", "array": _arr_to_json(obj.data),
                "t0": int(obj.t0)}
    return None


def container_from_jsonable(blob):
    """Inverse of ``container_to_jsonable`` (numpy-leafed result).  Raises
    ``ValueError`` on unknown kinds; key/shape errors propagate as the
    usual ``KeyError``/``TypeError`` for the caller's skip-with-warning
    policy."""
    kind = blob.get("kind") if isinstance(blob, dict) else None
    if kind == "dense":
        return DenseTensor(_arr_from_json(blob["array"]),
                           valid_count=int(blob["valid_count"]),
                           fill=float(blob["fill"]))
    if kind == "columnar":
        return ColumnarTable({c: _arr_from_json(v)
                              for c, v in blob["columns"].items()},
                             valid=np.asarray(blob["valid"], bool))
    if kind == "coo":
        return COOMatrix(_arr_from_json(blob["rows"]),
                         _arr_from_json(blob["cols"]),
                         _arr_from_json(blob["vals"]),
                         (int(blob["shape"][0]), int(blob["shape"][1])))
    if kind == "stream":
        return StreamBuffer(_arr_from_json(blob["array"]),
                            t0=int(blob.get("t0", 0)))
    raise ValueError(f"unknown container kind {kind!r}")
