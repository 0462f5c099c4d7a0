"""Process placement: the persistent compile cache's directory, and the
worker pool's refusal to start where its workers could not get a chip."""
import os

import jax
import numpy as np
import pytest

from repro.core import device
from repro.core.middleware import BigDAWG
from repro.core.tables import DenseTensor
from repro.runtime.server import QueryServer


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch,
                                                       restore_cache_dir):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    path = device.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == device.DEFAULT_CACHE_DIR == os.path.join(root,
                                                            ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert device.enable_compile_cache() == path     # idempotent


def _registered_bigdawg():
    bd = BigDAWG(train_plans=1)
    bd.register("M", DenseTensor(np.ones((4, 3), np.float32)), "dense_array")
    return bd


def test_pool_refuses_a_parent_that_holds_a_chip(monkeypatch):
    # steer the check: pretend this process started the TPU backend
    monkeypatch.setattr(device, "initialised_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="already started the 'tpu'"):
        QueryServer(_registered_bigdawg(), processes=2)


def test_pool_refuses_more_workers_than_chips(monkeypatch):
    monkeypatch.setattr(device, "initialised_backend", lambda: None)
    monkeypatch.setattr(device, "tpu_chips", lambda: 1)
    with pytest.raises(ValueError, match="exceeds the 1 TPU chips"):
        QueryServer(_registered_bigdawg(), processes=2)


def test_pool_placement_on_a_chip_host(monkeypatch):
    monkeypatch.setattr(device, "initialised_backend", lambda: None)
    monkeypatch.setattr(device, "tpu_chips", lambda: 4)
    assert device.check_pool_placement(4) == 4
    monkeypatch.setattr(device, "tpu_chips", lambda: 0)
    assert device.check_pool_placement(3) == 0     # CPU host: no pinning


def test_pin_to_chip_sets_a_one_chip_slice(monkeypatch):
    env = {}                   # this process's real environment stays as is
    monkeypatch.setattr(os, "environ", env)
    device.pin_to_chip(2)
    device.pin_to_chip(2)      # a respawned worker re-pins the same chip
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert int(env["TPU_PROCESS_PORT"]) > 0
