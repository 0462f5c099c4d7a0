"""Public jit'd wrappers for the Pallas kernels.

Dispatch policy: on a TPU backend the Pallas kernels always run compiled;
on any other backend (the CPU test container) the pure-jnp references in
``kernels/ref.py`` execute.  The kernels themselves are validated against
those references in interpret mode (``interpret=True``) by
tests/test_kernels.py, and compiled for a described TPU by
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.haar import haar_pallas
from repro.kernels.knn import knn_pallas, knn_scores_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_intra_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def haar(x: jnp.ndarray, levels: int) -> jnp.ndarray:
    """Haar DWT over the last axis of an array of any rank."""
    if not _on_tpu():
        return ref.haar_ref(x, levels)
    rows = x.reshape(-1, x.shape[-1])
    return haar_pallas(rows, levels).reshape(x.shape)


def knn(train: jnp.ndarray, test: jnp.ndarray, k: int):
    if not _on_tpu():
        return ref.knn_ref(train, test, k)
    return knn_pallas(train, test, k)


def knn_scores(train, test):
    if not _on_tpu():
        return ref.knn_scores_ref(train, test)
    return knn_scores_pallas(train, test)


def flash_attention(q, k, v, *, causal: bool = True):
    """q, k, v: (BH, S, d)."""
    if not _on_tpu():
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return flash_attention_pallas(q, k, v, causal=causal)


def ssd_intra(x, da, B, C):
    if not _on_tpu():
        return ref.ssd_intra_ref(x, da, B, C)
    return ssd_intra_pallas(x, da, B, C)
