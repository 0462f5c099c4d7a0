"""Pallas TPU kernel: kNN score matrix (test @ trainᵀ) as a tiled MXU matmul.

Tiling: (block_b × V) @ (V × block_n) per grid step.  The contraction runs
over the whole feature width V in one block (the analytic's V is 224; up to
``_MAX_V`` fits VMEM), so no partial block ever enters a sum.  ``block_n``
is a multiple of 128 (lane-dense output tiles), or all of N when N is
smaller; ``block_b`` is a multiple of 8 or all of B, so one test row
(B = 1) is a legal block.  N and B that the blocks do not divide get
partial last blocks: their out-of-range rows and columns are never written
back.  Top-k runs outside the kernel (it is O(B·N) and bandwidth-trivial
next to the GEMM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_MAX_V = 2048                 # widest feature axis held in one block


def _scores_kernel(t_ref, x_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        t_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _block(block: int, dim: int, align: int) -> int:
    """``block`` rounded down to ``align``, or all of ``dim`` if that is
    no larger."""
    b = max(align, block - block % align)
    return dim if dim <= b else b


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_n", "interpret"))
def knn_scores_pallas(train: jnp.ndarray, test: jnp.ndarray,
                      block_b: int = 128, block_n: int = 512,
                      interpret: bool = False):
    """train: (N, V); test: (B, V) -> scores (B, N) f32."""
    n, v = train.shape
    b = test.shape[0]
    if v > _MAX_V:
        raise ValueError(f"knn_scores_pallas: V={v} > {_MAX_V} does not fit "
                         f"one VMEM block")
    bb, bn = _block(block_b, b, 8), _block(block_n, n, 128)
    return pl.pallas_call(
        _scores_kernel,
        grid=(pl.cdiv(b, bb), pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bb, v), lambda i, j: (i, 0)),   # test block
            pl.BlockSpec((bn, v), lambda i, j: (j, 0)),   # train block
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
    )(test, train)


def knn_pallas(train, test, k, interpret: bool = False):
    scores = knn_scores_pallas(train, test, interpret=interpret)
    s, idx = jax.lax.top_k(scores, k)
    return idx, s
