"""The benchmark harness: one run of one cell.

Everything that belongs to one configuration, one traffic mix or one metric
sits in files of its own, found by the names in ``BENCHMARK.json``:

``configs/<config>.json``   sizes, limits and source (the manifest's ``file``)
``configs/<config>.py``     ``setup``, ``reference`` and ``control``
``traffic/<traffic>.json``  the parameters ``loadgen`` reads
``end_to_end/<metric>.py``  ``read(run)`` for an end-to-end metric
``layers/<metric>.py``      ``read(run)`` for a per-layer metric

A reader returns a number, or None where it finds nothing to read (the
metric is then left out of the result line).

A run: set-up (data, registration, training, warm serves, then
``loadgen.WARM_S`` seconds of the cell's own traffic), the measured window, the device's peak
memory, then the program's state is freed and the plain reference checks
the answers served in the window.  With ``--trace 1`` the program records
its span trees and a profiler trace is taken in the middle of the window.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_tmp", "trace")
PROFILE_SECONDS = 4.0
OP_NAME_CHARS = 160         # a device op's HLO text, cut for the breakdown


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


# -- finding things by name ----------------------------------------------------

def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of the manifest, with everything it names resolved."""
    name: str
    chips: int
    config: Dict[str, Any]            # the configuration's JSON file
    config_mod: Any                   # its setup/reference/control module
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]  # manifest entries reported here
    per_layer: List[Dict[str, Any]]
    bench_dir: str


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, manifest_path: str = MANIFEST,
            bench_dir: str = BENCH) -> Cell:
    """The cell called ``name`` in the manifest, its files loaded."""
    man = load_json(manifest_path)
    wl = {w["name"]: w for w in man["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r}; known: {', '.join(wl)}")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in man["configs"]}[w["config"]]
    root = os.path.dirname(os.path.abspath(manifest_path))
    cfg_file = os.path.join(root, cfg_entry["file"])
    mod_file = os.path.splitext(cfg_file)[0] + ".py"
    return Cell(
        name=name, chips=int(w["chips"]), config=load_json(cfg_file),
        config_mod=load_module(mod_file, "bench_config_"
                               + w["config"].replace("-", "_")),
        traffic=load_json(os.path.join(bench_dir, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=[m for m in man["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in man["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def reader(bench_dir: str, kind: str, metric: str) -> Callable:
    """The ``read`` of ``<kind>/<metric>.py``.  A metric split by the
    end-to-end metric it moves (``serve_ms.closed``) is read by its base
    name's file (``serve_ms.py``) where the split has no file of its own."""
    path = os.path.join(bench_dir, kind, metric + ".py")
    if not os.path.exists(path) and "." in metric:
        path = os.path.join(bench_dir, kind, metric.split(".")[0] + ".py")
    return load_module(path, f"bench_{kind}_"
                       + metric.replace(".", "_").replace("-", "_")).read


# -- the process: compile cache, chips, compiles -----------------------------

def configure_jax_env() -> None:
    """Before JAX is imported: the persistent compilation cache at a fixed
    directory of the checkout, for every program however short its compile
    (the program's own ``enable_compile_cache`` reads the same variable)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def check_chips(chips: int) -> List[Any]:
    """The local devices, or ``NoChip`` when they are not TPUs or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs


class Compiles:
    """Counts XLA backend compiles (JAX's monitoring events)."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        from jax import monitoring

        def on(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += secs
        monitoring.register_event_duration_secs_listener(on)


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- what a run hands the readers ---------------------------------------------

def log(tag: str, **fields) -> None:
    """One diagnostic line on standard error."""
    print(f"{tag}: " + json.dumps(fields, default=str), file=sys.stderr,
          flush=True)


@dataclass
class Ctx:
    """What a configuration's ``setup`` is told."""
    seed: int
    trace: bool
    rehearse: bool

    @staticmethod
    def log(tag: str, **fields) -> None:
        log(tag, **fields)


@dataclass
class Run:
    """One run's record, which every metric reader reduces."""
    cell: Cell
    window: Any                         # loadgen.Window
    setup_s: float
    train_walls: List[float]            # host seconds of each training serve
    device_kind: str
    memory_peak: int
    profile: Any = None                 # trace_reduce.Reduced, traced runs
    extra: Dict[str, Any] = field(default_factory=dict)


def summarize(rep) -> Dict[str, Any]:
    """The Report's fields the readers use, without its result (whose
    device buffers must not outlive the request)."""
    trace = getattr(rep, "trace", None)
    return {"seconds": float(rep.seconds),
            "cast_bytes": float(rep.cast_bytes), "mode": rep.mode,
            "plan_key": rep.plan_key,
            "spans": trace.to_dict()["spans"] if trace is not None else None}


class Profiler:
    """A profiler trace of ``seconds`` in the middle of the window, on a
    thread of its own.  ``active`` is when the profiler held the host, from
    the call that starts it to the return of the one that writes the trace
    (seconds from the window's start)."""

    def __init__(self, window_s: float, seconds: float = PROFILE_SECONDS):
        self.seconds = min(seconds, window_s / 2)
        self.delay = max(0.0, (window_s - self.seconds) / 2)
        self.window_ns = 0.0
        self.active: Optional[Tuple[float, float]] = None
        self.thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None

    def start(self, t0: float) -> None:
        self.thread = threading.Thread(target=self._run, args=(t0,),
                                       daemon=True, name="bench-profiler")
        self.thread.start()

    def _run(self, t0: float) -> None:
        import jax
        try:
            time.sleep(max(0.0, t0 + self.delay - time.perf_counter()))
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            start = time.perf_counter()
            jax.profiler.start_trace(TRACE_DIR)
            a = time.perf_counter()
            time.sleep(self.seconds)
            b = time.perf_counter()
            jax.profiler.stop_trace()
            self.active = (start - t0, time.perf_counter() - t0)
            self.window_ns = (b - a) * 1e9
        except BaseException as exc:   # noqa: BLE001 — reported by join()
            self.error = exc

    def join(self, timeout: float = 300.0) -> None:
        if self.thread is not None:
            self.thread.join(timeout)
        if self.error is not None:
            raise self.error


# -- one run -------------------------------------------------------------------

@dataclass
class Prepared:
    """A cell set up and ready for its window."""
    cell: Cell
    cfg: Dict[str, Any]
    ctx: Ctx
    devs: List[Any]
    compiles: Compiles
    system: Any


def prepare(cell: Cell, seed: int, trace: bool = False,
            rehearse: bool = False) -> Prepared:
    """The JAX process set up (compile cache, chips, compile counter) and
    the configuration's ``setup``: data, registration, training, warm
    serves."""
    configure_jax_env()
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices() if rehearse else check_chips(cell.chips)
    compiles = Compiles()
    ctx = Ctx(seed=seed, trace=trace, rehearse=rehearse)
    cfg = dict(cell.config)
    if rehearse:
        cfg.update(cfg.get("rehearsal", {}))
    system = cell.config_mod.setup(cfg, ctx)
    return Prepared(cell, cfg, ctx, devs, compiles, system)


def seeded_streams(seed: int, n: int):
    import numpy as np
    ss = np.random.SeedSequence([abs(int(seed)), int(seed < 0), 7])
    return [np.random.default_rng(s) for s in ss.spawn(n)]


def warm_up(prep: Prepared, traffic: Dict[str, Any], rng) -> None:
    """``loadgen.WARM_S`` seconds of the cell's own traffic, as set-up."""
    import loadgen
    warm_s = loadgen.WARM_S
    c0 = prep.compiles.count
    warm = loadgen.drive(traffic, prep.system.serve, warm_s, rng)
    prep.ctx.log("warmup", seconds=warm_s, requests=len(warm.outcomes),
                 failed=warm.failed, compiles=prep.compiles.count - c0,
                 errors=sorted({o.error for o in warm.outcomes
                                if o.error})[:3])


def measure(prep: Prepared, traffic: Dict[str, Any], seconds: float, rng,
            kept: Optional[List[Tuple[int, Any]]] = None,
            profiler: Optional[Profiler] = None):
    """One window of the traffic, each answer appended to ``kept``; returns
    (window, seconds-from-perf-clock of its start, compiles inside it)."""
    import loadgen
    marks: Dict[str, float] = {}

    def serve(v):
        rep, answer = prep.system.serve(v)
        return summarize(rep), answer

    def finish(o):
        if kept is not None:
            kept.append((o.variant, o.answer))
        o.answer = None

    def at_start(t0):
        marks["t0"] = t0
        marks["compiles"] = prep.compiles.count
        if profiler is not None:
            profiler.start(t0)

    window = loadgen.drive(traffic, serve, seconds, rng, at_start=at_start,
                           finish=finish)
    compiles = prep.compiles.count - marks["compiles"]
    modes: Dict[str, int] = {}
    plans: Dict[str, int] = {}
    for o in window.outcomes:
        if o.ok:
            modes[o.report["mode"]] = modes.get(o.report["mode"], 0) + 1
            plans[o.report["plan_key"]] = plans.get(o.report["plan_key"],
                                                    0) + 1
    prep.ctx.log("window", seconds=seconds, attempted=len(window.outcomes),
                 failed=window.failed, compiles=compiles, modes=modes,
                 plans=plans, errors=sorted({o.error for o in window.outcomes
                                             if o.error})[:3])
    return window, marks["t0"], compiles


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, t_start: Optional[float] = None,
        keep_host: bool = False) -> Dict[str, Any]:
    """Set up, measure one window, check it; returns the result object
    (plus, with ``keep_host``, the configuration and the reference's data
    under ``_cfg`` and ``_host``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    prep = prepare(cell, seed, trace, rehearse)
    warm_rng, win_rng = seeded_streams(seed, 2)
    warm_up(prep, cell.traffic, warm_rng)
    kept: List[Tuple[int, Any]] = []
    profiler = Profiler(seconds) if trace else None
    window, t0, _ = measure(prep, cell.traffic, seconds, win_rng, kept,
                            profiler)
    setup_s = t0 - t_start
    if profiler is not None:
        profiler.join()
    peak = memory_peak_bytes()
    answers = [(v, prep.system.to_host(a)) for v, a in kept]
    train_walls = list(prep.system.train_walls)
    cfg, host, devs = prep.cfg, prep.system.host, prep.devs
    prep.system.close()
    del prep, kept
    gc.collect()
    profile = None
    if profiler is not None:
        import trace_reduce
        t = time.perf_counter()
        profile = trace_reduce.reduce(trace_reduce.find_xplane(TRACE_DIR),
                                      profiler.window_ns, cpu_ops=rehearse)
        log("profile", seconds=time.perf_counter() - t,
            window_s=profile.window_ns / 1e9, busy_s=profile.busy_ns / 1e9,
            ops=len(profile.ops))

    t = time.perf_counter()
    checks = cell.config_mod.reference(cfg, host, answers)
    log("reference", seconds=time.perf_counter() - t, answers=len(answers))
    checks = {"unanswered": (float(window.failed), 0.0), **checks}
    record = Run(cell=cell, window=window, setup_s=setup_s,
                 train_walls=train_walls,
                 device_kind=devs[0].device_kind, memory_peak=peak,
                 profile=profile,
                 extra={"cfg": cfg,
                        "profiled": profiler.active if profiler else None})
    kind, entries = ("layers", cell.per_layer) if trace else \
        ("end_to_end", cell.end_to_end)
    metrics = {}
    for m in entries:
        value = reader(cell.bench_dir, kind, m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {
        "correct": bool(window.outcomes) and all(
            math.isfinite(v) and v <= lim for v, lim in checks.values()),
        "attempted": len(window.outcomes), "failed": window.failed,
        "metrics": metrics, "device": device}
    if profile is not None:
        device["busy_s"] = profile.busy_ns / 1e9
        device["window_s"] = profile.window_ns / 1e9
        result["breakdown"] = {
            "device_ops": [[n[:OP_NAME_CHARS], s] for n, s in profile.top_ops],
            "idle_gaps": [[n, s] for n, s in profile.idle_gaps]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    if keep_host:
        result["_cfg"], result["_host"] = cfg, host
    return result


def print_checks(result: Dict[str, Any]) -> None:
    """Each number compared beside its limit: the last lines on stderr."""
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
