"""``bin_hist_us`` reads the device time of one call of the dense engine's
histogram program from the trace, and nothing where there is none."""
import time
from collections import Counter
from types import SimpleNamespace

import numpy as np
import pytest

import trace_reduce as tr
from harness import BENCH, reader

ATTR = 'frontend_attributes={bigdawg_op="dense_bin_hist"}'


def _run(calls_ns):
    """A profile of Haar, a fused segment's reduction, then each histogram
    call's ops: a fusion per band and the copy that lays out the output,
    named by their HLO text as a TPU trace names them."""
    ops = [tr.Op("%haar_pallas.1 = f32[2048,16384] custom-call(...), "
                 "frontend_attributes={kernel_metadata={}}", 0, 5e6),
           tr.Op("%convert_reduce_fusion.1 = f32[2048,224] fusion(...), "
                 "kind=kLoop, calls=%fused_computation.1", 6e6, 2e5)]
    for i, durs in enumerate(calls_ns):
        t = 10e6 * (i + 1)
        for k, d in enumerate(durs):
            ops.append(tr.Op(f"%convert_reduce_fusion.{k} = f32[2048,32] "
                             f"fusion(...), kind=kLoop, "
                             f"calls=%fused_computation.{k}, {ATTR}", t, d))
            t += d
        ops.append(tr.Op("%copy = f32[2048,224]{0,1} copy(%concatenate.1), "
                         + ATTR, t, 4e3))
    return SimpleNamespace(profile=SimpleNamespace(ops=ops))


def test_bin_hist_us_sums_the_ops_of_a_call():
    run = _run([[400e3, 200e3], [500e3, 100e3]])
    # (600 + 4 + 600 + 4) us over 2 calls
    assert reader(BENCH, "layers", "bin_hist_us")(run) == pytest.approx(604.0)
    assert reader(BENCH, "layers", "bin_hist_us.closed")(run) == \
        pytest.approx(604.0)


def test_bin_hist_us_finds_nothing_without_the_program():
    assert reader(BENCH, "layers", "bin_hist_us")(_run([])) is None
    assert reader(BENCH, "layers", "bin_hist_us")(
        SimpleNamespace(profile=None)) is None


def test_bin_hist_us_on_a_recorded_profile(tmp_path):
    """Three calls of the engine op in a CPU profile: the reader finds every
    op of the program three times by its module's name, and reads a third
    of their time."""
    import jax
    import jax.numpy as jnp
    from repro.core.engines import ENGINES
    from repro.core.tables import DenseTensor
    x = DenseTensor(jnp.asarray(np.random.default_rng(0).normal(
        size=(64, 1024)).astype(np.float32)))

    def call():
        ENGINES["dense_array"].run("bin_hist", {"nbins": 32, "levels": 6},
                                   x).data.block_until_ready()

    call()
    jax.profiler.start_trace(str(tmp_path))
    t0 = time.perf_counter()
    for _ in range(3):
        call()
    wall_us = (time.perf_counter() - t0) * 1e6
    jax.profiler.stop_trace()
    profile = tr.reduce(tr.find_xplane(str(tmp_path)), 600e9, cpu_ops=True)
    ops = tr.matching(profile.ops, "jit_dense_bin_hist")
    assert ops and set(Counter(op.name for op in ops).values()) == {3}
    got = reader(BENCH, "layers", "bin_hist_us")(
        SimpleNamespace(profile=profile))
    assert got == pytest.approx(sum(op.dur for op in ops) / 3 / 1e3)
    assert 0 < got < wall_us
