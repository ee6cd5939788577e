"""What every cell shares: finding a cell's files by name, the chip
check, the compile cache, host spans, the program's counters, the
per-layer metric readers, and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Its
configuration is ``bench/configs/<config>.json`` (whose ``kind`` picks
the driver module ``bench/<kind>.py``), its traffic mix is
``bench/traffic/<traffic>.json``, and each per-layer metric ``<name>``
is read by ``read(ctx)`` in ``bench/metrics/<name>.py``. Adding a cell,
a mix or a metric is adding files and a ``workloads`` entry.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> Dict:
    """The cell's ``workloads`` entry with its configuration and traffic
    files loaded, plus the per-layer metrics it reports."""
    bj = benchmark(root)
    cells = {w["name"]: w for w in bj["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    cell = dict(cells[name])
    bench = os.path.join(root, "bench")
    cell["cfg"] = load_json(os.path.join(bench, "configs",
                                         cell["config"] + ".json"))
    cell["mix"] = load_json(os.path.join(bench, "traffic",
                                         cell["traffic"] + ".json"))
    e2e = [m for m in bj["end_to_end"]
           if name in m.get("workloads", [name])]
    cell["end_to_end"] = e2e
    names = {m["name"] for m in e2e}
    cell["per_layer"] = [m for m in bj["per_layer"]
                         if (name in m["workloads"] if "workloads" in m
                             else m["moves"] in names)]
    return cell


def metric_reader(name: str, root: str = ROOT) -> Callable[[Dict], object]:
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver(kind: str):
    """The driver module for a configuration ``kind`` (``bench/<kind>
    .py``), exposing ``run(cell, seed, seconds, trace, log)``."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(kind)


def require_chips(n: int) -> Dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default platform is "
                     f"{devs[0].platform!r}; this benchmark measures "
                     "the chip only")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def configure_compile_cache() -> str:
    """JAX's persistent compile cache at the checkout's fixed place
    (``JAX_COMPILATION_CACHE_DIR`` when set), every program cached."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import configure_compile_cache as cc
    where = cc()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts XLA backend compiles (cache misses compile; hits do not)
    while ``on``; JAX logs the name of each program it compiles then."""

    def __init__(self):
        import jax.monitoring as mon
        self._on = False
        self.n = 0

        def listen(event, duration, **kw):
            if self._on and "backend_compile" in event:
                self.n += 1
        mon.register_event_duration_secs_listener(listen)

    @property
    def on(self) -> bool:
        return self._on

    @on.setter
    def on(self, value: bool) -> None:
        import jax
        self._on = bool(value)
        jax.config.update("jax_log_compiles", self._on)


class Spans:
    """Host spans of the benchmark's own calls into each layer: a
    ``jax.profiler.TraceAnnotation`` (named ``bench.<name>``) for the
    device trace, and summed host-clock seconds per name while
    ``on``."""

    def __init__(self):
        self.on = False
        self.total: Dict[str, float] = {}
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        if self.on:
            d = time.perf_counter() - t0
            with self._lock:
                self.total[name] = self.total.get(name, 0.0) + d


def counters() -> Dict[str, float]:
    """The program's counters and histogram sums/counts the per-layer
    metrics read, as a flat dict (labels after a ``|``)."""
    from repro.core.telemetry import get_telemetry
    tel = get_telemetry()
    out: Dict[str, float] = {}
    snap = tel.snapshot(traces=False)
    for name, fam in snap["metrics"].items():
        if fam["type"] == "gauge":
            continue
        for s in fam["series"]:
            lab = ",".join(f"{k}={v}" for k, v in
                           sorted(s["labels"].items()))
            key = name + ("|" + lab if lab else "")
            if "sum" in s:
                out[key + ":sum"] = float(s["sum"])
                out[key + ":count"] = float(s["count"])
            else:
                out[key] = float(s["value"])
    return out


def delta(after: Dict[str, float], before: Dict[str, float]
          ) -> Dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


class Tracer:
    """The device trace of the measured window (``--trace 1``): host
    Python tracing off, the benchmark's annotations and the device ops
    on."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.dir = out_dir

    def start(self):
        if not self.enabled:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if self.enabled:
            import jax
            jax.profiler.stop_trace()


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict], device: Dict,
                checks: List[Dict], breakdown: Optional[Dict] = None
                ) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return json.dumps(line)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def memory_peak() -> int:
    """Peak bytes in use on the fullest chip so far."""
    import jax
    peaks = [int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for dev in jax.local_devices()]
    return max(peaks) if peaks else 0
