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


# ---------------------------------------------------------------------------
# the dense engine's per-band histograms (core/engines.py dense_bin_hist), one
# compiled program a call, against numpy's count of the same bin formula and
# against that formula run op by op as a one-hot and its sum, exactly, with
# values planted at every bin edge and a few ulps either side of it, at lo
# and hi, and outside them
# ---------------------------------------------------------------------------

def _np_bin_hist(x, nbins, levels, lo, hi):
    from repro.core.engines import _scale_slices
    with np.errstate(over="ignore", invalid="ignore"):
        u = (x - np.float32(lo)) / np.float32(hi - lo) * np.float32(nbins)
    idx = np.clip(np.trunc(u), 0, nbins - 1).astype(np.int64)
    return np.concatenate(
        [np.stack([(idx[:, off:off + ln] == b).sum(1) for b in range(nbins)],
                  1) for off, ln in _scale_slices(x.shape[1], levels)],
        1).astype(np.float32)


def _op_by_op_bin_hist(x, nbins, levels, lo, hi):
    """The dense engine's bin_hist as eager ops, band by band."""
    from repro.core.engines import _scale_slices
    outs = []
    for off, ln in _scale_slices(x.shape[1], levels):
        idx = jnp.clip(((x[:, off:off + ln] - lo) / (hi - lo) * nbins)
                       .astype(jnp.int32), 0, nbins - 1)
        outs.append(jnp.einsum("nlb->nb", jax.nn.one_hot(idx, nbins,
                                                         dtype=jnp.float32)))
    return jnp.concatenate(outs, axis=1)


def _planted(n, t, nbins, lo, hi, seed):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((n, t)) * (hi - lo) / 3).astype(np.float32)
    edges = np.float32(lo) + np.float32((hi - lo) / nbins) * np.arange(
        nbins + 1, dtype=np.float32)
    near = [edges]
    for way in (np.float32(-np.inf), np.float32(np.inf)):
        v = edges
        for _ in range(4):
            v = np.nextafter(v, way)
            near.append(v)
    plant = np.concatenate(near + [np.float32(
        [lo, hi, lo - 1, hi + 1, 1e30, -1e30, np.inf, -np.inf])])
    pos = rng.choice(x.size, size=min(x.size, 4 * plant.size), replace=False)
    x.ravel()[pos] = np.resize(plant, pos.size)
    return x


def _dense_bin_hist(x, **attrs):
    from repro.core.engines import ENGINES
    from repro.core.tables import DenseTensor
    return np.asarray(ENGINES["dense_array"].run(
        "bin_hist", attrs, DenseTensor(jnp.asarray(x))).data)


@pytest.mark.parametrize("n,t,levels,nbins,lo,hi", [
    (41, 16384, 6, 32, -3.0, 3.0),   # the analytic's width; 41 = 32 + 9 rows
    (600, 1024, 6, 32, -3.0, 3.0),   # 600 rows
    (128, 128, 2, 8, -3.0, 3.0),     # the cost model's calibration probe
    (64, 1024, 4, 16, -3.0, 3.0),    # tests/test_system.py: 64-wide bands
    (13, 256, 3, 7, -1.3, 2.9),      # other lo/hi, odd bin count
    (20, 192, 6, 32, -3.0, 3.0),     # T not a multiple of 128: 3-wide bands
    (5, 64, 2, 4, -0.5, 0.25),       # fewer rows than a sublane group
])
def test_bin_hist(n, t, levels, nbins, lo, hi):
    x = _planted(n, t, nbins, lo, hi, seed=n * t)
    want = _np_bin_hist(x, nbins, levels, lo, hi)
    got = _dense_bin_hist(x, nbins=nbins, levels=levels, lo=lo, hi=hi)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(_op_by_op_bin_hist(jnp.asarray(x), nbins, levels, lo, hi)),
        want)
    assert want.sum(1).tolist() == [t] * n


def test_bin_hist_defaults_and_one_program():
    """Without lo/hi the op bins over [-3, 3), and a call is one dispatch:
    its jaxpr is a single ``jit`` of ``dense_bin_hist``."""
    from repro.core.engines import _da_bin_hist
    from repro.core.tables import DenseTensor
    x = _planted(16, 256, 32, -3.0, 3.0, seed=3)
    np.testing.assert_array_equal(_dense_bin_hist(x, nbins=32, levels=3),
                                  _np_bin_hist(x, 32, 3, -3.0, 3.0))
    jaxpr = jax.make_jaxpr(lambda v: _da_bin_hist(
        {"nbins": 32, "levels": 3}, DenseTensor(v)).data)(jnp.asarray(x))
    assert [e.primitive.name for e in jaxpr.eqns] == ["jit"]
    assert jaxpr.eqns[0].params["name"] == "dense_bin_hist"


def test_dense_bin_hist_equals_columnar():
    """The dense engine's histograms equal the columnar engine's
    sort/segment ones on the same coefficients."""
    from repro.core.cast import dense_to_columnar
    from repro.core.engines import ENGINES
    from repro.core.tables import DenseTensor
    x = _planted(48, 1024, 32, -3.0, 3.0, seed=7)
    # the columnar engine's numpy cast to int32 wraps what lies beyond it
    x = np.clip(x, -100.0, 100.0)
    attrs = {"nbins": 32, "levels": 6}
    d = DenseTensor(jnp.asarray(x))
    dense = np.asarray(ENGINES["dense_array"].run("bin_hist", attrs, d).data)
    col = ENGINES["columnar"].run("bin_hist", attrs, dense_to_columnar(d))
    i, j = np.asarray(col.columns["i"]), np.asarray(col.columns["j"])
    got = np.zeros_like(dense)
    got[i, j] = np.asarray(col.columns["value"])
    np.testing.assert_array_equal(dense, got)
