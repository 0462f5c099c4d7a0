"""Pallas kernel validation: interpret=True vs pure-jnp oracle, swept over
shapes and dtypes (assignment requirement: per-kernel allclose against ref)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.haar import haar_pallas
from repro.kernels.knn import knn_pallas, knn_scores_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_intra_pallas

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,t,levels", [(4, 16, 2), (8, 64, 3), (130, 256, 4),
                                        (3, 32, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_haar(n, t, levels, dtype):
    x = jax.random.normal(KEY, (n, t), dtype)
    got = haar_pallas(x, levels, block_rows=8, interpret=True)
    want = ref.haar_ref(x, levels)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_haar_energy_preserved():
    """Orthonormal transform property: ||coeffs|| == ||signal||."""
    x = jax.random.normal(KEY, (16, 128), jnp.float32)
    y = haar_pallas(x, 4, interpret=True)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=1),
                               np.linalg.norm(np.asarray(x), axis=1),
                               rtol=1e-5)


@pytest.mark.parametrize("n,v,b", [(64, 128, 4), (256, 512, 8), (100, 48, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_knn_scores(n, v, b, dtype):
    train = jax.random.normal(KEY, (n, v), dtype)
    test = jax.random.normal(jax.random.PRNGKey(1), (b, v), dtype)
    got = knn_scores_pallas(train, test, interpret=True)
    want = ref.knn_scores_ref(train, test)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=0.3 if dtype == jnp.bfloat16 else 1e-3)


def test_knn_topk_indices():
    train = jax.random.normal(KEY, (128, 64), jnp.float32)
    test = jax.random.normal(jax.random.PRNGKey(2), (2, 64), jnp.float32)
    idx, _ = knn_pallas(train, test, 5, interpret=True)
    idx_ref, _ = ref.knn_ref(train, test, 5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))


@pytest.mark.parametrize("bh,s,d", [(2, 128, 64), (1, 256, 128), (4, 64, 32)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(bh, s, d, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (bh, s, d), dtype)
    k = jax.random.normal(ks[1], (bh, s, d), dtype)
    v = jax.random.normal(ks[2], (bh, s, d), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=64,
                                 block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("b,q,h,p,g,n", [(2, 32, 4, 16, 1, 8),
                                         (1, 64, 8, 32, 2, 16),
                                         (2, 128, 6, 64, 1, 64)])
def test_ssd_intra(b, q, h, p, g, n):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (b, q, h, p), jnp.float32)
    da = -jax.nn.softplus(jax.random.normal(ks[1], (b, q, h)))  # negative decay
    B = jax.random.normal(ks[2], (b, q, g, n), jnp.float32)
    C = jax.random.normal(ks[3], (b, q, g, n), jnp.float32)
    y, st, cd = ssd_intra_pallas(x, da, B, C, block_h=4, interpret=True)
    y2, st2, cd2 = ref.ssd_intra_ref(x, da, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st2), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(cd), np.asarray(cd2), rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# randomized-shape/dtype differential parity: every Pallas kernel vs its
# kernels/ref.py oracle (plan-level fusion routes warm serves through these
# kernels, so the fused path is only as trustworthy as this battery).
# Shapes are drawn from a seeded RNG — deterministic, but not hand-picked —
# and each kernel's own block-size adaptation must absorb whatever is drawn.
# ---------------------------------------------------------------------------

_RAND_SEEDS = list(range(6))


def _rand_dtype(rng):
    return jnp.bfloat16 if rng.integers(0, 2) else jnp.float32


@pytest.mark.parametrize("seed", _RAND_SEEDS)
def test_haar_random_shapes(seed):
    rng = np.random.default_rng(seed)
    levels = int(rng.integers(1, 5))
    n = int(rng.integers(1, 200))
    t = int(rng.integers(1, 17)) * (1 << levels)   # T % 2^levels == 0
    dtype = _rand_dtype(rng)
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, t), dtype)
    got = haar_pallas(x, levels, block_rows=int(rng.integers(1, 129)),
                      interpret=True)
    want = ref.haar_ref(x, levels)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("seed", _RAND_SEEDS)
def test_knn_scores_random_shapes(seed):
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(2, 300))
    v = int(rng.integers(2, 200))
    b = int(rng.integers(1, 12))
    dtype = _rand_dtype(rng)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    train = jax.random.normal(ks[0], (n, v), dtype)
    test = jax.random.normal(ks[1], (b, v), dtype)
    got = knn_scores_pallas(train, test, interpret=True)
    want = ref.knn_scores_ref(train, test)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=0.3 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.parametrize("seed", _RAND_SEEDS)
def test_knn_topk_random_shapes(seed):
    # float32 only: bfloat16 score ties reorder the top-k indices, which is
    # an ordering artifact, not a kernel defect
    rng = np.random.default_rng(200 + seed)
    n = int(rng.integers(8, 300))
    v = int(rng.integers(2, 200))
    b = int(rng.integers(1, 8))
    k = int(rng.integers(1, min(n, 8)))
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    train = jax.random.normal(ks[0], (n, v), jnp.float32)
    test = jax.random.normal(ks[1], (b, v), jnp.float32)
    idx, score = knn_pallas(train, test, k, interpret=True)
    idx_ref, score_ref = ref.knn_ref(train, test, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    np.testing.assert_allclose(np.asarray(score), np.asarray(score_ref),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("seed", _RAND_SEEDS)
def test_flash_attention_random_shapes(seed):
    rng = np.random.default_rng(300 + seed)
    bh = int(rng.integers(1, 5))
    s = int(rng.integers(1, 40)) * 8
    d = int(rng.integers(4, 80))
    causal = bool(rng.integers(0, 2))
    dtype = _rand_dtype(rng)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (bh, s, d), dtype)
    k = jax.random.normal(ks[1], (bh, s, d), dtype)
    v = jax.random.normal(ks[2], (bh, s, d), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=64,
                                 block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("seed", _RAND_SEEDS)
def test_ssd_intra_random_shapes(seed):
    rng = np.random.default_rng(400 + seed)
    b = int(rng.integers(1, 4))
    q = int(rng.integers(1, 12)) * 8
    h = int(rng.integers(1, 10))
    p = int(rng.integers(2, 40))
    g = int(rng.integers(1, 3))
    while h % g:                              # heads group evenly
        g = 1
    n = int(rng.integers(2, 40))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (b, q, h, p), jnp.float32)
    da = -jax.nn.softplus(jax.random.normal(ks[1], (b, q, h)))
    B = jax.random.normal(ks[2], (b, q, g, n), jnp.float32)
    C = jax.random.normal(ks[3], (b, q, g, n), jnp.float32)
    y, st, cd = ssd_intra_pallas(x, da, B, C, block_h=4, interpret=True)
    y2, st2, cd2 = ref.ssd_intra_ref(x, da, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st2), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(cd), np.asarray(cd2), rtol=1e-5,
                               atol=1e-6)


def test_ssd_intra_matches_full_ssd():
    """One-chunk SSD == the model's chunked SSD with chunk == seq."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n = 2, 64, 4, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.2)
    B = jax.random.normal(ks[3], (b, s, 1, n))
    C = jax.random.normal(ks[4], (b, s, 1, n))
    y_full, _ = ssd_chunked(x, dt, A, B, C, chunk=s)
    da = dt * A
    y_k, _, _ = ssd_intra_pallas(x * dt[..., None], da, B, C, block_h=4,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_full, np.float32),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# block tails: row counts the row block does not divide (a partial last
# block, never written back past the end), the MXU-chunked Haar levels of
# long rows, and knn's 128-multiple train tiles with a ragged tail and B = 1
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,t,levels,block_rows", [
    (41, 1024, 6, 8),        # 5 full blocks + 1 row; 2 chunked levels
    (600, 512, 6, 128),      # the analytic's patient count, 1 chunked level
    (9, 2048, 3, 8),         # chunked levels only: no dense tail
])
def test_haar_partial_last_block(n, t, levels, block_rows):
    x = jax.random.normal(jax.random.PRNGKey(n), (n, t), jnp.float32)
    got = haar_pallas(x, levels, block_rows=block_rows, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.haar_ref(x, levels)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,v,b,block_n", [
    (600, 224, 1, 128),      # the analytic: one test patient, ragged tail
    (300, 96, 11, 128),      # B not a multiple of 8
    (200, 2048, 3, 128),     # the widest V one block holds
])
def test_knn_scores_partial_tiles(n, v, b, block_n):
    ks = jax.random.split(jax.random.PRNGKey(v), 2)
    train = jax.random.normal(ks[0], (n, v), jnp.float32)
    test = jax.random.normal(ks[1], (b, v), jnp.float32)
    got = knn_scores_pallas(train, test, block_n=block_n, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.knn_scores_ref(train, test)),
                               rtol=1e-4, atol=1e-3)
