"""Pallas TPU kernel: multi-level Haar DWT over rows of a (N, T) array.

The TPU's vector unit cannot take the even/odd lanes of a row apart (a
strided lane slice is refused by the Mosaic compiler), so the de-interleave
runs on the MXU instead: one level over a 256-sample chunk is ``chunk @ P``
with ``P`` a 0/±1 matrix whose first 128 columns sum adjacent pairs and whose
last 128 columns difference them.  Both halves are 128 lanes wide, so every
store lands on a lane-aligned offset: approximations go to a VMEM scratch row
that the next level reads, details go straight to their band of the output.
Levels whose input is not a multiple of 256 wide (short rows, or the last
levels of a row) are finished by one matmul with the dense matrix of the
remaining transform.

Precision: ``P`` is exact in bfloat16, and the input is split into three
bfloat16 terms (8 + 8 + 8 mantissa bits), so three single-pass MXU products
accumulated in f32 reproduce the f32 sums and differences exactly; the
``1/sqrt(2)`` scale is applied afterwards on the VPU, as the jnp reference
does.

Tiling: a grid step owns ``br`` whole rows.  ``br`` is a multiple of 8 chosen
from ``T`` so that the double-buffered input and output blocks plus the
scratch row stay under ``_VMEM_BUDGET`` (well inside v5e's 16 MiB default
scoped VMEM; 32 rows at T = 16,384).  A row count that ``br`` does not divide
gets a partial last block: its out-of-range rows are never written back, and
rows are independent, so no padding copy is needed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_CHUNK = 256                       # samples per MXU de-interleave step
_VMEM_BUDGET = 12 * 1024 * 1024    # bytes: 2x in + 2x out blocks + scratch
_MAX_TAIL = 2048                   # widest dense remaining-transform matrix


def _pair_matrix() -> np.ndarray:
    """(256, 256) 0/±1: ``x @ P = [x[2i] + x[2i+1] | x[2i] - x[2i+1]]``."""
    h = _CHUNK // 2
    p = np.zeros((_CHUNK, _CHUNK), np.float32)
    i = np.arange(h)
    p[2 * i, i] = p[2 * i + 1, i] = 1.0
    p[2 * i, h + i] = 1.0
    p[2 * i + 1, h + i] = -1.0
    return p


def _tail_matrix(w: int, levels: int) -> np.ndarray:
    """(w, w) dense matrix of the ``levels``-level transform of a w-sample
    row, output in the kernel's band order [a_L, d_L, ..., d_1]."""
    inv = 1.0 / math.sqrt(2.0)
    a = np.eye(w)                            # columns: the w unit inputs
    details = []
    for _ in range(levels):
        e, o = a[:, 0::2], a[:, 1::2]
        details.append((e - o) * inv)
        a = (e + o) * inv
    return np.concatenate([a] + details[::-1], axis=1).astype(np.float32)


def _split3(x):
    """x (f32) as hi + mid + lo, each exactly a bfloat16."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _exact_dot(x, m_bf16):
    """f32-exact ``x @ m`` for a matrix exact in bfloat16 (3 MXU passes)."""
    return sum(jnp.dot(part, m_bf16, preferred_element_type=jnp.float32)
               for part in _split3(x))


def _plan(t: int, levels: int):
    """Split the levels into MXU-chunked ones and a dense tail."""
    chunked, w = 0, t
    while chunked < levels and w % _CHUNK == 0 and w > _CHUNK:
        chunked += 1
        w //= 2
    tail = levels - chunked
    if tail and w > _MAX_TAIL:
        raise ValueError(f"haar_pallas: a {w}-sample tail after {chunked} "
                         f"chunked levels is too wide (T={t}, "
                         f"levels={levels})")
    return chunked, w, tail


def _haar_kernel(*refs, t: int, chunked: int, tail: int):
    if tail:
        x_ref, p_ref, m_ref, o_ref, a_ref = refs
    else:
        x_ref, p_ref, o_ref, a_ref = refs
    inv = jnp.float32(1.0 / math.sqrt(2.0))
    h = _CHUNK // 2
    src, w = x_ref, t
    for _ in range(chunked):
        half = w // 2

        def step(c, carry, src=src, half=half):
            lo = pl.multiple_of(c * _CHUNK, _CHUNK)
            y = _exact_dot(src[:, pl.ds(lo, _CHUNK)].astype(jnp.float32),
                           p_ref[...]) * inv
            out = pl.multiple_of(c * h, h)
            # approximations overwrite lanes already consumed (out < lo + h)
            a_ref[:, pl.ds(out, h)] = y[:, :h]
            o_ref[:, pl.ds(half + out, h)] = y[:, h:].astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, w // _CHUNK, step, 0)
        src, w = a_ref, half
    if tail:
        a = src[:, :w].astype(jnp.float32)
        o_ref[:, :w] = jnp.dot(a, m_ref[...], preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST
                               ).astype(o_ref.dtype)
    else:
        o_ref[:, :w] = src[:, :w].astype(o_ref.dtype)


def _row_block(n: int, t: int, itemsize: int, block_rows: int) -> int:
    per_row = 4 * t * itemsize + 4 * t         # 2x in, 2x out, f32 scratch
    br = min(block_rows, _VMEM_BUDGET // per_row)
    br = max(8, br - br % 8)
    return n if n <= br else br


@functools.partial(jax.jit, static_argnames=("levels", "block_rows", "interpret"))
def haar_pallas(x: jnp.ndarray, levels: int, block_rows: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    n, t = x.shape
    assert t % (1 << levels) == 0, "T must be divisible by 2^levels"
    chunked, w, tail = _plan(t, levels)
    br = _row_block(n, t, x.dtype.itemsize, block_rows)
    consts = [jnp.asarray(_pair_matrix(), jnp.bfloat16)]
    in_specs = [pl.BlockSpec((br, t), lambda i: (i, 0)),
                pl.BlockSpec((_CHUNK, _CHUNK), lambda i: (0, 0))]
    if tail:
        consts.append(jnp.asarray(_tail_matrix(w, tail)))
        in_specs.append(pl.BlockSpec((w, w), lambda i: (0, 0)))
    return pl.pallas_call(
        functools.partial(_haar_kernel, t=t, chunked=chunked, tail=tail),
        grid=(pl.cdiv(n, br),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, t), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, t), x.dtype),
        scratch_shapes=[pltpu.VMEM((br, max(t // 2, 1)), jnp.float32)],
        interpret=interpret,
    )(x, *consts)
