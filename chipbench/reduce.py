"""From a profiler trace to device busy time, kernel time and host spans.

A traced run profiles a short sub-window of its window (``Tracer``).  The
trace is read with ``jax.profiler.ProfileData`` and reduced to three lists
of ``[name, start_ns, duration_ns]`` on the trace's clock:

- ``ops``: the device's XLA operations (Pallas kernels among them);
- ``modules``: the device's executions of whole jitted programs;
- ``spans``: the harness's own host annotations (``chipbench.*``).

``Reduced`` answers the questions the per-layer metrics ask of them.  The
reduction runs on this plain form, so a small recorded trace checks it
(tests/test_reduce.py).
"""
from __future__ import annotations

import bisect
import json
import re
import shutil
import tempfile
from collections import defaultdict

from chipbench import harness

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."


def union(intervals, lo, hi) -> float:
    """Length of the union of [start, end) intervals, clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


class Reduced:
    def __init__(self, data: dict):
        self.ops = [tuple(x) for x in data["ops"]]
        self.modules = [tuple(x) for x in data["modules"]]
        self.spans = [tuple(x) for x in data["spans"]]
        self.window = tuple(data["window"])

    # -- whole window ------------------------------------------------------

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        lo, hi = self.window
        return union([(s, s + d) for _, s, d in self.ops], lo, hi) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # -- programs and kernels ----------------------------------------------

    def module_runs(self, pattern: str) -> list:
        """Executions of jitted programs whose name matches ``pattern``."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return [(n, s, d) for n, s, d in self.modules
                if rx.search(n) and lo <= s and s + d <= hi]

    def ops_within(self, runs) -> list:
        """Device ops that start inside one of the given module runs."""
        starts = sorted((s, s + d) for _, s, d in runs)
        keys = [s for s, _ in starts]
        out = []
        for op in self.ops:
            j = bisect.bisect_right(keys, op[1]) - 1
            if j >= 0 and op[1] < starts[j][1]:
                out.append(op)
        return out

    def kernel_s(self, op_pattern: str, runs) -> float:
        """Summed device seconds of ops matching ``op_pattern`` inside the
        given module runs."""
        rx = re.compile(op_pattern)
        return sum(d for n, _, d in self.ops_within(runs)
                   if rx.search(n)) * 1e-9

    def host_spans(self, name: str) -> list:
        lo, hi = self.window
        return [(n, s, d) for n, s, d in self.spans
                if n == name and lo <= s and s + d <= hi]

    def busy_within(self, start: float, end: float) -> float:
        return union([(s, s + d) for _, s, d in self.ops], start, end) * 1e-9

    # -- what the next issue's writer sees ---------------------------------

    def breakdown(self, n: int = 10) -> dict:
        by = defaultdict(float)
        lo, hi = self.window
        for name, s, d in self.ops:
            if lo <= s < hi:
                by[_family(name)] += d * 1e-9
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps(n)]}

    def idle_gaps(self, n: int = 10) -> list:
        """The longest stretches with no device op, each named by the
        innermost harness span that covers its middle."""
        lo, hi = self.window
        busy = sorted((max(s, lo), min(s + d, hi)) for _, s, d in self.ops
                      if s + d > lo and s < hi)
        gaps, cur = [], lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = 0.5 * (s + e)
            cover = [(d, nm) for nm, ss, d in self.spans if ss <= mid < ss + d]
            label = min(cover)[1] if cover else "no harness span"
            out.append((label, (e - s) * 1e-9))
        return out


def op_name(event_name: str) -> str:
    """The HLO instruction's name from a device op event, which carries the
    whole instruction (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _family(name: str) -> str:
    """An op's name without its numeric suffix (fusion.12 -> fusion)."""
    return re.sub(r"[.\d]+$", "", name) or name


def reduce_profile(path: str, window_name: str = SPAN_PREFIX + "window",
                   device: int = 0) -> Reduced:
    """Read one ``.xplane.pb`` and reduce it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and int(m.group(1)) == device:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    name = op_name(ev.name) if dest is ops else ev.name
                    dest.append((name, ev.start_ns, ev.duration_ns))
            elif plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.duration_ns))
    win = [s for s in spans if s[0] == window_name]
    if not win:
        raise RuntimeError(f"trace has no {window_name!r} span")
    _, s, d = win[0]
    return Reduced({"ops": ops, "modules": modules, "spans": spans,
                    "window": (s, s + d)})


class Tracer:
    """Profiles ``spec['seconds']`` of the window, from ``spec['start_s']``
    (capped to fit), driven by the window loop's ``on_step(now)``."""

    def __init__(self, spec: dict, window_seconds: float):
        self.length = float(spec["seconds"])
        self.start_at = min(float(spec["start_s"]),
                            max(0.0, window_seconds - self.length))
        self.dir = None
        self.state = "idle"
        self.annotation = None
        self.t_on = self.t_off = None  # on the window's clock

    def on_step(self, now: float) -> None:
        import jax

        if self.state == "idle" and now >= self.start_at:
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.dir)
            self.annotation = jax.profiler.TraceAnnotation(
                SPAN_PREFIX + "window")
            self.annotation.__enter__()
            self.t_on, self.stop_at = now, now + self.length
            self.state = "on"
        elif self.state == "on" and now >= self.stop_at:
            self.stop(now)

    def stop(self, now: float | None = None) -> None:
        import jax

        if self.state != "on":
            return
        self.t_off = now
        self.annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def reduced(self, now: float) -> Reduced:
        import glob

        self.stop(now)
        if self.dir is None:
            raise RuntimeError("the traced sub-window never started")
        try:
            paths = glob.glob(f"{self.dir}/plugins/profile/*/*.xplane.pb")
            return reduce_profile(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def read_metrics(spec: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell, by its own reader; a reader that
    finds nothing returns None and the metric is left out."""
    out = {}
    for m in spec["per_layer"]:
        value = harness.module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def load(path: str) -> Reduced:
    with open(path) as f:
        return Reduced(json.load(f))
