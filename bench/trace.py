"""Reduction of a profiler trace to the benchmark's device numbers.

``collect`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``, into plain lists: per device plane the events
of its ops line and of its programs line, and the benchmark's own host spans
(``bench.*`` ``TraceAnnotation`` events). ``reduce`` works on those lists
alone, so it can be checked on a small recorded trace:

* busy: the union of the device's op intervals inside the traced window,
  averaged over the devices used; the idle share is 1 - busy / window;
* program time: the summed device time of the programs whose name holds one
  of a reader's name fragments;
* the device ops that took most time, named ``program/op``, counting an
  op that holds nested ops (a loop) by its nested ops alone;
* the idle gaps, attributed to the innermost host span around each gap's
  midpoint (what the host was doing while the device waited).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINES = ("XLA Ops",)
PROGRAM_LINES = ("XLA Modules",)
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


def find_xplane(log_dir: str | Path) -> Path | None:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def collect(xplane: str | Path) -> dict:
    """Plain events of one trace: {"devices": {plane: {"ops": [...],
    "programs": [...]}}, "spans": [...], "lines": {plane: [line names]}},
    each event ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane))
    devices: dict[str, dict] = {}
    spans: list = []
    lines: dict[str, list] = {}
    for plane in pd.planes:
        names = [ln.name for ln in plane.lines]
        if names:
            lines[plane.name] = names
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "programs": []}
            for ln in plane.lines:
                kind = ("ops" if ln.name in OPS_LINES else
                        "programs" if ln.name in PROGRAM_LINES else None)
                if kind:
                    dev[kind] += [[e.name, float(e.start_ns),
                                   float(e.duration_ns)] for e in ln.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in ln.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans, "lines": lines}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: list, lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float | None          # None: the trace holds no device ops
    devices: int
    program_s: dict               # program name -> seconds (device mean)
    top_ops: list                 # [[program/op, seconds], ...]
    idle_gaps: list               # [[host span, seconds], ...]

    @property
    def idle_share(self) -> float | None:
        if self.busy_s is None or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, fragments) -> float | None:
        """Device seconds of the programs whose name holds one of
        ``fragments``; None when no such program ran."""
        hit = [v for k, v in self.program_s.items()
               if any(f in k for f in fragments)]
        return sum(hit) if hit else None


def reduce(ev: dict) -> Reduction:
    spans = ev["spans"]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    elif spans:
        lo = min(s[1] for s in spans)
        hi = max(s[1] + s[2] for s in spans)
    else:
        raise ValueError("trace holds no bench.* span")
    window_s = (hi - lo) / 1e9
    devs = [d for d in ev["devices"].values() if d["ops"]]
    if not devs:
        return Reduction(window_s, None, 0, {}, [], [])
    n = len(devs)
    busy = 0.0
    program_s: dict = defaultdict(float)
    op_s: dict = defaultdict(float)
    gaps: dict = defaultdict(float)
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                   key=lambda s: s[2])          # shortest span first
    for di, dev in enumerate(devs):
        ops = sorted(_clip(dev["ops"], lo, hi), key=lambda x: x[1])
        merged = _union([(a, b) for _, a, b in ops])
        busy += sum(b - a for a, b in merged)
        progs = sorted(_clip(dev["programs"], lo, hi), key=lambda x: x[1])
        for name, a, b in progs:
            program_s[_program(name)] += (b - a) / 1e9 / n
        j = 0
        for k, (name, a, b) in enumerate(ops):
            if k + 1 < len(ops) and ops[k + 1][1] < b:
                continue        # holds nested ops: count its leaves only
            while j + 1 < len(progs) and progs[j + 1][1] <= a:
                j += 1
            owner = (_program(progs[j][0])
                     if progs and progs[j][1] <= a < progs[j][2] else "-")
            op_s[f"{owner}/{_op(name)}"] += (b - a) / 1e9 / n
        if di == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    mid = (a + b) / 2
                    host = next((s[0] for s in inner
                                 if s[1] <= mid < s[1] + s[2]), "other")
                    gaps[host] += (b - a) / 1e9
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduction(window_s, busy / 1e9 / n, n, dict(program_s),
                     [[k, v] for k, v in top], [[k, v] for k, v in idle])


def _op(name: str) -> str:
    """``%fusion.12 = (u32[...]) fusion(...)`` -> ``fusion.12``: an op's
    name without its HLO text."""
    return name.split(" = ", 1)[0].lstrip("%")


def _program(name: str) -> str:
    """``jit_f(123)`` -> ``jit_f``: a program's name without its id."""
    return re.sub(r"\(\d+\)$", "", name)
