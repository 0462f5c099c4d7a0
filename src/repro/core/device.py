"""Where a process's JAX work runs: the persistent compile cache, one TPU
chip per pool worker, and the clock anchor that puts the program's spans on
a device profile's clock.

A TPU chip belongs to one process at a time.  A process that has started a
JAX backend on a chip holds every chip its runtime opened until it exits,
so a ``ProcPool`` master must stay off JAX (its catalog mirror and merges
are numpy) and each worker must be told which chip is its own before its
first JAX call.  Nothing in this module starts a backend: chips are counted
on the PCI bus, as JAX itself does before it opens them.
"""
from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Any, Dict, Optional

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout (and ignored by git): the directory is part of
# the cache key, so a per-run path would never hit
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")

_CHIP_PORT_BASE = 8476        # TPU_PROCESS_PORT of the worker on chip 0

# name of the annotation a profile carries for each traced request
ANCHOR = "bigdawg.clock_anchor"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``DEFAULT_CACHE_DIR``.
    Call before the process's first compile; calling again is a no-op."""
    path = os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def initialised_backend() -> Optional[str]:
    """Platform of this process's default JAX backend, or None while no
    backend has started (asking does not start one)."""
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return None
    return jax.default_backend()


def tpu_chips() -> int:
    """TPU chips attached to this host that JAX may use here: 0 where
    ``JAX_PLATFORMS`` leaves the TPU out."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def check_pool_placement(processes: int) -> int:
    """Refuse a worker pool that could not get its chips; return the number
    of chips the workers are pinned to (0 off a TPU host).

    Raises ``RuntimeError`` when this process already started a backend on
    an accelerator (its workers would fail or hang opening the chip), and
    ``ValueError`` when there are fewer chips than workers."""
    backend = initialised_backend()
    if backend not in (None, "cpu"):
        raise RuntimeError(
            f"cannot start {processes} worker processes: this process has "
            f"already started the {backend!r} JAX backend and holds its "
            f"chips.  Build the pool (connect(processes=...), "
            f"QueryServer(..., processes=...)) before any JAX work in this "
            f"process, and register numpy containers with it.")
    chips = tpu_chips()
    if chips and processes > chips:
        raise ValueError(f"processes={processes} exceeds the {chips} TPU "
                         f"chips on this host: each worker needs a chip of "
                         f"its own")
    return chips


def pin_to_chip(idx: int) -> None:
    """Make TPU chip ``idx`` the only chip this process's runtime opens.
    Must run before the process's first JAX call.  A one-chip slice per
    process also lifts the runtime's one-process-per-host lock, so workers
    pinned to different chips start side by side."""
    os.environ.update({
        "TPU_VISIBLE_CHIPS": str(idx),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(_CHIP_PORT_BASE + idx),
    })


def describe() -> dict:
    """This process's first JAX device, and the chip it was pinned to."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "id": d.id,
            "count": len(jax.devices()), "pid": os.getpid(),
            "chip": os.environ.get("TPU_VISIBLE_CHIPS")}


def memory_stats() -> Dict[str, Any]:
    """This process's first device's ``memory_stats()`` (``{}`` where the
    backend reports none)."""
    return jax.devices()[0].memory_stats() or {}


def clock_anchor(span) -> None:
    """While a profiler traces this process, write one ``ANCHOR``
    annotation into the profile between two ``perf_counter`` readings,
    recorded as a ``clock_anchor`` child of ``span``.  Its ``request`` stat
    is ``r`` and the span's id: the letter keeps the profiler from reading
    an id such as ``703e-12`` as a number.  The two clocks differ by one
    offset, so a reader that pairs the annotation with the record maps
    every span of every request onto the profile's clock.  Does nothing
    while no profiler runs."""
    if not jax.profiler.TraceAnnotation.is_enabled():
        return
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(ANCHOR, request="r" + span.sid):
        pass
    span.static_child("clock_anchor", t0, time.perf_counter() - t0)
