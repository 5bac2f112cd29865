"""Reduce a profiler trace of one run's window to the numbers the
benchmark reports: device busy time (the union of the intervals in which
an operation ran), the idle share, device time per operation name, and
the longest idle gaps, each named by the harness span around it and the
innermost host event at its middle.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict of events; ``reduce`` works on that dict alone, so it can be
checked against a small recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import pathlib
import re
import shutil

# Names of the harness's own host spans (``jax.profiler.TraceAnnotation``).
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
# Lines of a device plane: one event per operation run, and one per
# program (XLA module) run.
LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    op_seconds: dict          # op name -> device seconds in the window
    gaps: list                # [(label, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_xplane(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path) -> dict:
    """{"device": {plane: {"ops": [[op, start_ns, dur_ns], ...],
                          "modules": [[program, start_ns, dur_ns], ...]}},
        "host": [[name, start_ns, dur_ns, thread], ...]} from one
    ``.xplane.pb``.  Device planes are those named ``/device:...``; their
    ``XLA Ops`` and ``XLA Modules`` lines are read.  Host events are every
    event of the host plane's threads."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                key = LINES.get(line.name)
                if key:
                    device.setdefault(plane.name, {"ops": [], "modules": []})
                    device[plane.name][key].extend(
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns, line.name]
                            for e in line.events)
    return {"device": device, "host": host}


def op_name(hlo: str) -> str:
    """``fusion.1 f32[54595584] fusion kCustom`` from an op event's HLO
    text ``%fusion.1 = f32[54595584]{0:T(1024)} fusion(...), kind=kCustom``:
    name, result shape without layout (``tuple`` for a tuple), opcode and
    fusion kind."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{", 1)[0]
    opcode = rest.split("(", 1)[0]
    kind = re.search(r"kind=(k\w+)", rest)
    return " ".join([name.lstrip("%"), shape, opcode]
                    + ([kind.group(1)] if kind else []))


def _self_times(ops):
    """Per op event, its duration less that of the events nested in it
    (a ``while`` holds its body's ops): (name, start, end, self_ns)."""
    out, stack = [], []
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        e = s + d
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, e, d]
        if stack and e <= stack[-1][2]:
            stack[-1][3] -= d
        out.append(rec)
        stack.append(rec)
    return out


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(host, t, thread):
    """The innermost harness span that contains time ``t``, and the
    innermost other event of the harness's thread that does."""
    span, inner = (WINDOW_SPAN, float("inf")), None
    for name, s, d, line in host:
        if line == thread and s <= t <= s + d and name != WINDOW_SPAN:
            if name.startswith(SPAN_PREFIX):
                if d < span[1]:
                    span = (name, d)
            elif inner is None or d < inner[1]:
                inner = (name, d)
    return f"{span[0]} > {inner[0]}" if inner else span[0]


def _module_of(modules):
    """time -> name of the program running on the device then."""
    mods = sorted((s, s + d, name.split("(", 1)[0]) for name, s, d in modules)
    starts = [m[0] for m in mods]

    def find(t):
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][2] if i >= 0 and t < mods[i][1] else "?"
    return find


def reduce(events: dict) -> Summary:
    """Busy, idle and op times inside the harness's window span,
    averaged over the device planes that ran anything.  An op's time is
    its self time, named ``<program>/<op_name>``."""
    windows = [(s, s + d, line) for name, s, d, line in events["host"]
               if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0, w1, thread = windows[0]
    planes = [p for p in events["device"].values() if p["ops"]]
    if not planes:
        raise ValueError("no device operation in the trace")
    busy_ns, op_ns, gaps = 0.0, {}, []
    for plane in planes:
        module = _module_of(plane["modules"])
        clipped = []
        for name, s, e, self_ns in _self_times(plane["ops"]):
            s0, e0 = max(s, w0), min(e, w1)
            if e0 > s0:
                clipped.append((s0, e0))
                key = f"{module(s)}/{op_name(name)}"
                op_ns[key] = op_ns.get(key, 0.0) + self_ns * (e0 - s0) / (e - s)
        merged = _union(clipped)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    n = len(planes)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(events["host"], (s + e) / 2, thread), (e - s) / 1e9)
                for s, e in gaps[:10]]
    return Summary(busy_s=busy_ns / n / 1e9, window_s=(w1 - w0) / 1e9,
                   op_seconds={k: v / n / 1e9 for k, v in op_ns.items()},
                   gaps=labelled)


class Tracer:
    """Profiles the window of a ``--trace 1`` run into ``trace_dir`` and
    marks the harness's spans on the host."""

    def __init__(self, trace_dir):
        self.dir = pathlib.Path(trace_dir)

    @contextlib.contextmanager
    def window(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.dir))
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    def span(self, name):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def reduce(self) -> Summary:
        return reduce(load(find_xplane(self.dir)))


class NoTracer:
    """Tracing off: the spans cost nothing."""

    def window(self):
        return contextlib.nullcontext()

    def span(self, name):
        return contextlib.nullcontext()
