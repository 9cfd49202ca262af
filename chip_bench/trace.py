"""Device trace: capture with ``jax.profiler``, and the reduction from the
trace to busy and idle time, time per scope, and idle gaps by host span.

The capture writes an ``.xplane.pb`` under a temporary directory. ``load``
turns it into a small dict that the reductions read, and that the tests
keep as fixtures:

    {"device": [[name, module, start_ns, dur_ns, scope], ...],  # per op
     "modules": [[name, start_ns, dur_ns], ...],                # per run
     "spans": [[name, start_ns, dur_ns], ...],                  # host
     "devices": n}

On a TPU v5e under jax 0.9 an op event is named by its whole HLO
instruction (``%fusion.12 = bf16[...] fusion(...)``) and carries no module
or scope stat; the module is the ``XLA Modules`` event (one per program
run, ``jit_prefill_step(<fingerprint>)``) that holds the op's start.
``scope`` is the op's ``op_name`` metadata, the ``jax.named_scope`` path it
was traced under (``.../dual_engine.fused_layer/...``), joined from the
compiled program's HLO text by module and instruction name. Host spans are
the benchmark's own ``TraceAnnotation``s, named ``bench.*``. Device and
host times share one clock only to about a millisecond (a program has
been seen to start 1 ms before the host span that dispatched it), so
per-wave numbers are taken as window totals over wave counts, and the
naming of idle gaps by host span is approximate at that scale.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."

_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')
_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_EVENT_OP = re.compile(r"^%?([\w.\-]+)")


def hlo_scopes(hlo_text: str) -> Dict[Tuple[str, str], str]:
    """(module, instruction) -> op_name, from a compiled program's text."""
    out: Dict[Tuple[str, str], str] = {}
    module = ""
    for line in hlo_text.splitlines():
        m = _HLO_MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = _HLO_OP.match(line)
        if m:
            out[(module, m.group(1))] = m.group(2)
    return out


class Capture:
    """Start and stop the profiler around a window; ``load`` afterwards."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self):
        import jax
        # no Python function tracer: it records every call of the host
        # loop and slows it several times over
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def load(self, scopes: Optional[Dict] = None) -> Dict:
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        try:
            return load(paths[0], scopes)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def module_base(name: str) -> str:
    """``jit_prefill_step(1533...)`` -> ``jit_prefill_step``."""
    return name.split("(", 1)[0]


def load(path: str, scopes: Optional[Dict] = None) -> Dict:
    """Read an ``.xplane.pb`` into the compact dict described above."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    scopes = scopes or {}
    device, modules, spans = [], [], []
    n_dev = 0
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            n_dev += 1
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted(([module_base(ev.name), int(ev.start_ns),
                            int(ev.duration_ns)]
                           for ev in lines.get(MODULES_LINE, [])),
                          key=lambda m: m[1])
            modules += mods
            starts = [m[1] for m in mods]
            for ev in lines.get(OPS_LINE, []):
                t = int(ev.start_ns)
                i = bisect.bisect_right(starts, t) - 1
                mod = mods[i][0] if i >= 0 and t < mods[i][1] + mods[i][2] \
                    else ""
                op = _EVENT_OP.match(ev.name).group(1)
                device.append([op, mod, t, int(ev.duration_ns),
                               scopes.get((mod, op), "")])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIX)]
    if n_dev == 0:
        raise RuntimeError("the trace holds no TPU device plane")
    return {"device": device, "modules": modules, "spans": spans,
            "devices": n_dev}


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(merged: List[Tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) that the merged intervals cover."""
    return sum(e - s for s, e in clip(merged, lo, hi))


class Trace:
    """A loaded trace, cut to the ``bench.window`` span."""

    def __init__(self, data: Dict, window: str = "bench.window"):
        wins = [s for s in data["spans"] if s[0] == window]
        if len(wins) != 1:
            raise ValueError(f"expected one {window!r} span, found "
                             f"{len(wins)}")
        _, w0, wd = wins[0]
        self.lo, self.hi = w0, w0 + wd
        self.devices = data["devices"]
        self.ops = [o for o in data["device"]
                    if o[2] < self.hi and o[2] + o[3] > self.lo]
        self.spans = [s for s in data["spans"]
                      if s[0] != window and s[1] >= self.lo
                      and s[1] + s[2] <= self.hi]
        self.busy = union((o[2], o[2] + o[3]) for o in self.ops)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        return covered(self.busy, self.lo, self.hi) * 1e-9 / self.devices

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def scope_s(self, *prefixes: str) -> float:
        """Device seconds of ops whose scope path has a component that
        starts with one of ``prefixes`` (``"sparse_engine."``)."""
        def hit(scope):
            return any(part.startswith(prefixes) for part in scope.split("/"))
        merged = union((o[2], o[2] + o[3]) for o in self.ops if hit(o[4]))
        return covered(merged, self.lo, self.hi) * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` op labels with the most device self time (seconds):
        an op that holds others (a ``while`` around a scanned layer) counts
        only the time in which none of them runs. An op with no scope is
        labelled by its program (``jit_argmax/select_reduce_fusion``)."""
        acc: Dict[str, int] = {}
        stack: List[List] = []            # [end, label, self ns]

        def close(entry):
            acc[entry[1]] = acc.get(entry[1], 0) + entry[2]
        for name, mod, s, d, scope in sorted(self.ops,
                                             key=lambda o: (o[2], -o[3])):
            s0, e0 = max(s, self.lo), min(s + d, self.hi)
            while stack and stack[-1][0] <= s0:
                close(stack.pop())
            if stack:
                stack[-1][2] -= max(0, min(e0, stack[-1][0]) - s0)
            label = op_label(name, scope)
            if not scope and mod:
                label = f"{mod}/{label}"
            stack.append([e0, label, e0 - s0])
        for entry in stack:
            close(entry)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest idle gaps, each named by the innermost host
        span that covers its midpoint ("no span" where none does)."""
        gaps, t = [], self.lo
        for s, e in clip(self.busy, self.lo, self.hi):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if self.hi > t:
            gaps.append((t, self.hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for lo, hi in gaps[:n]:
            mid = (lo + hi) // 2
            inner = [s for s in self.spans if s[1] <= mid < s[1] + s[2]]
            name = min(inner, key=lambda s: s[2])[0] if inner else "no span"
            out.append([name, (hi - lo) * 1e-9])
        return out


def op_label(name: str, scope: str) -> str:
    """A readable, stable label for an op: its innermost ``bench``-visible
    named scope and the last component of its op_name, else its name."""
    name = re.sub(r"\.\d+$", "", name)
    if not scope:
        return name
    parts = [p for p in scope.split("/") if not p.startswith(("jit(",
                                                              "while",
                                                              "body",
                                                              "closed_call",
                                                              "checkpoint",
                                                              "remat"))]
    named = [p for p in parts if "." in p and not p[0].isdigit()]
    tail = parts[-1] if parts else name
    return f"{named[-1]}/{tail}" if named and named[-1] != tail else tail
