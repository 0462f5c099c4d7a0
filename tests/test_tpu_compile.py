"""Compile-only rehearsals of the main path's Pallas kernels for a described
TPU v5e (no chip needed): the compiler must accept ``haar_pallas`` and
``knn_scores_pallas`` at the analytic's real widths, including row counts
the blocks do not divide, and emit them as Mosaic kernels.

The topology is described inside a module-scoped fixture — never at import
— so every test worker collects the same tests and only the worker running
this file loads the TPU compiler.  The persistent compilation cache is off
around the compiles: an entry compiled for a described chip cannot be read
back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.haar import haar_pallas
from repro.kernels.knn import knn_scores_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:              # noqa: BLE001 — no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("n", [8192, 600])
def test_haar_compiles_for_v5e(one_chip, n):
    compiled = jax.jit(lambda x: haar_pallas(x, 6)).lower(
        _spec((n, 16384), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [8192, 600])
def test_knn_scores_compile_for_v5e(one_chip, n):
    compiled = jax.jit(knn_scores_pallas).lower(
        _spec((n, 224), one_chip), _spec((1, 224), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
