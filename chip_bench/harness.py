"""The harness: finds a cell's files by the names in ``BENCHMARK.json``,
runs its set-up and its measured window, reads its metrics, decides
``correct``, and prints the result line.

A cell is one entry of ``workloads``. Its configuration file names the
model, its traffic file names the runner (``runners/<runner>.py``) and
the runner's parameters, and each per-layer metric is read by
``metrics/<name>.py``. Adding a cell, a mix or a metric adds files and
entries; nothing here changes.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import pathlib
import statistics
import sys
import time
from typing import Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
# The last seconds of a traced run's window are traced: a steady stretch,
# short enough that the trace stays small and its reduction quick.
TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(rel: str) -> Dict:
    with open(ROOT / rel) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    def __init__(self, bench: Dict, name: str, seed: int):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        self.name, self.seed = name, seed
        self.workload = cells[name]
        entry = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config = load_json(entry["file"])
        self.traffic = load_json(f"chip_bench/traffic/"
                                 f"{self.workload['traffic']}.json")
        self.chips = self.workload["chips"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def require_accelerator(chips: int) -> List:
    """The cell's devices; exits non-zero, printing no result, where JAX
    finds no TPU or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"needs {chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s)")
        sys.exit(3)
    return devices[:chips]


def compile_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` in the checkout),
    holding every program, so only a checkout's first run of a cell
    compiles."""
    import jax
    from repro.launch.compile_cache import setup_compile_cache
    log(f"compile cache {setup_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Names the programs traced, compiled or loaded from the cache while
    armed: inside a window there should be none."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in self.EVENTS:
            self.names.append(str(kw.get("fun_name", "?")))


def peak_memory(devices) -> Optional[int]:
    """Peak device bytes on the fullest chip, from ``memory_stats``: the
    peak of buffers in use plus the peak reserved for compiled programs'
    temporaries, which the TPU runtime counts apart from buffers."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(st["peak_bytes_in_use"]
                         + st.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def measure(runner, seconds: float, trace: bool, compiles: CompileCounter):
    """Drive ``runner.step()`` for ``seconds``. With ``trace`` the last
    TRACE_SECONDS are traced: the runner drains, the profiler starts, a
    ``bench.window`` span opens, and both close after a final drain.
    Returns (t0, t1, traced counter deltas or None, capture or None)."""
    import jax
    from chip_bench.trace import Capture
    capture = counts0 = traced = None
    trace_from = max(0.0, seconds - TRACE_SECONDS)
    compiles.armed = True
    ends = []
    with contextlib.ExitStack() as tracing:
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            if trace and capture is None and \
                    time.perf_counter() - t0 >= trace_from:
                runner.drain()
                capture = Capture()
                capture.start()
                tracing.callback(capture.stop)
                tracing.enter_context(
                    jax.profiler.TraceAnnotation("bench.window"))
                counts0 = dict(runner.counters())
            with jax.profiler.TraceAnnotation("bench.step"):
                runner.step()
            ends.append(time.perf_counter())
            if ends[-1] >= end:
                break
        runner.drain()
        t1 = time.perf_counter()
    compiles.armed = False
    log(step_times(t0, ends))
    if capture is not None:
        traced = {k: v - counts0.get(k, 0)
                  for k, v in runner.counters().items()}
    return t0, t1, traced, capture


def step_times(t0: float, ends: List[float], n: int = 5) -> str:
    """One line on how the window's time fell to its steps: their count,
    median and quartiles, and the ``n`` longest with when each ended, in
    seconds into the window. A run that lost time to one pause shows it
    as one long step; a chip or host that ran slower throughout shows it
    as a higher median."""
    d = [b - a for a, b in zip([t0] + ends, ends)]
    q1, med, q3 = statistics.quantiles(d, n=4) if len(d) > 1 else d * 3
    worst = sorted(range(len(d)), key=lambda i: -d[i])[:n]
    return (f"steps {len(d)}: median {1e3 * med:.3f} ms, quartiles "
            f"{1e3 * q1:.3f}-{1e3 * q3:.3f} ms; longest " + ", ".join(
                f"{1e3 * d[i]:.3f} ms at {ends[i] - t0:.3f} s"
                for i in worst))


class Reading:
    """What a per-layer metric reader gets: the reduced trace, the runner's
    counter deltas over the traced window, the cell, and the peaks."""

    def __init__(self, trace, counts: Dict, cell: Cell, peaks: Dict):
        self.trace, self.counts, self.cell = trace, counts, cell
        self.peaks = peaks


def read_per_layer(cell: Cell, reading: Reading) -> Dict:
    out = {}
    for i, m in enumerate(cell.per_layer):
        mod = load_module(HERE / "metrics" / f"{m['name']}.py",
                          f"chip_bench_metric_{i}")
        value = mod.read(reading)
        if value is None:
            continue
        if not math.isfinite(value):
            raise ValueError(f"{m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peaks_for(kind: str) -> Dict:
    table = load_json("chip_bench/peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"chip_bench/peaks.json")
    return table[kind]
