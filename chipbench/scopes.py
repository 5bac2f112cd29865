"""Device time by program scope, from the profile of a ``--trace 1`` run.

The program names its device work with ``jax.named_scope`` under
``repro.`` (``repro.gather_rhs``, ``repro.kernel``, ``repro.unpermute``,
``repro.halo``).  XLA keeps the scope in each instruction's ``op_name``,
and the TPU profiler writes that into the trace: a device plane's event
metadata, one entry per HLO instruction named by its HLO text (the name
``trace.load`` gives the op events), carries it as the stat ``tf_op``.
``jax.profiler.ProfileData`` does not expose event metadata, so
:func:`op_paths` reads those fields from the ``.xplane.pb`` itself.

:func:`reduce` sums each op's self time in the harness's window, clipped
as ``trace.reduce`` clips it, under the innermost ``repro.`` component
of its ``tf_op``, or under ``other``, averaged over the device planes
that ran anything: the scopes and ``other`` add up to the busy time.

A per-layer reader is handed the harness's ``trace.Summary``, not the
trace's path.  :func:`for_run` takes the newest trace under the
directory the harness's ``Tracer`` writes to, and only where its busy
and window times are the summary's own.
"""
from __future__ import annotations

import dataclasses
import pathlib

from chipbench import trace as T

PREFIX = "repro."
OTHER = "other"
# Where ``run.py``'s ``Tracer`` writes a cell's profile:
# ``.cache/trace/<cell>/plugins/profile/<stamp>/*.xplane.pb``.
TRACES = pathlib.Path(__file__).resolve().parent / ".cache" / "trace"

# Field numbers of tsl/profiler/protobuf/xplane.proto.
_SPACE_PLANE = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_META_NAME = 1, 2
_STAT_STR, _STAT_REF = 5, 7


@dataclasses.dataclass
class Scopes:
    busy_s: float
    window_s: float
    seconds: dict             # scope or OTHER -> device seconds in the window


def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b):
    """(field number, value) of each field of one protobuf message:
    an int for a varint, the bytes for the other wire types."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def op_paths(path) -> dict:
    """{device plane: {op event name (HLO text): tf_op}} from one
    ``.xplane.pb``; an op whose metadata has no ``tf_op`` is left out."""
    out = {}
    for num, plane in _fields(memoryview(pathlib.Path(path).read_bytes())):
        if num != _SPACE_PLANE:
            continue
        name, metas, stat_names = None, [], {}
        for f, v in _fields(plane):
            if f == _PLANE_NAME:
                name = _text(v)
                if not name.startswith("/device:"):
                    break
            elif f == _PLANE_EVENT_META:
                metas.append(dict(_fields(v)).get(_MAP_VALUE, b""))
            elif f == _PLANE_STAT_META:
                meta = dict(_fields(dict(_fields(v)).get(_MAP_VALUE, b"")))
                stat_names[meta.get(_STAT_META_ID, 0)] = _text(
                    meta.get(_STAT_META_NAME, b""))
        if name is None or not name.startswith("/device:"):
            continue
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        paths = out.setdefault(name, {})
        for meta in metas:
            op, stats = None, []
            for f, v in _fields(meta):
                if f == _META_NAME:
                    op = _text(v)
                elif f == _META_STATS:
                    stats.append(dict(_fields(v)))
            for st in stats:
                if st.get(_STAT_META_ID) in tf_op:
                    value = (_text(st[_STAT_STR]) if _STAT_STR in st
                             else stat_names.get(st.get(_STAT_REF), ""))
                    if op is not None and value:
                        paths[op] = value
    return out


def scope_of(tf_op: str | None) -> str:
    """The innermost ``repro.`` component of an op's path, or OTHER."""
    names = [c.rstrip(":") for c in (tf_op or "").split("/")
             if c.startswith(PREFIX)]
    return names[-1] if names else OTHER


def reduce(events: dict, paths: dict) -> Scopes:
    """Device seconds per scope in the window of ``events``
    (``trace.load``), each op's path from ``paths`` (:func:`op_paths`),
    averaged over the device planes that ran anything."""
    windows = [(s, s + d) for name, s, d, _ in events["host"]
               if name == T.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {T.WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    planes = {k: p for k, p in events["device"].items() if p["ops"]}
    if not planes:
        raise ValueError("no device operation in the trace")
    busy_ns, scope_ns = 0.0, {}
    for plane, p in planes.items():
        names = paths.get(plane, {})
        clipped = []
        for name, s, e, self_ns in T._self_times(p["ops"]):
            s0, e0 = max(s, w0), min(e, w1)
            if e0 > s0:
                clipped.append((s0, e0))
                scope = scope_of(names.get(name))
                scope_ns[scope] = (scope_ns.get(scope, 0.0)
                                   + self_ns * (e0 - s0) / (e - s))
        busy_ns += sum(e - s for s, e in T._union(clipped))
    n = len(planes)
    return Scopes(busy_s=busy_ns / n / 1e9, window_s=(w1 - w0) / 1e9,
                  seconds={k: v / n / 1e9 for k, v in scope_ns.items()})


def read(path) -> Scopes:
    return reduce(T.load(path), op_paths(path))


_read_cache: dict = {}


def for_run(ctx) -> Scopes | None:
    """The scopes of the run whose summary is ``ctx["trace"]``: the
    newest trace the harness wrote, if its busy and window times are the
    summary's; else None."""
    summary = ctx.get("trace")
    files = sorted(TRACES.glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda f: f.stat().st_mtime)
    if summary is None or not files:
        return None
    key = (str(files[-1]), files[-1].stat().st_mtime_ns)
    if key not in _read_cache:
        _read_cache.clear()
        _read_cache[key] = read(files[-1])
    sc = _read_cache[key]
    same = (abs(sc.busy_s - summary.busy_s) <= 1e-9 * max(1.0, sc.busy_s)
            and abs(sc.window_s - summary.window_s) <= 1e-9)
    return sc if same else None


def ms_per_apply(ctx, scope: str):
    """Device milliseconds per apply under ``scope``, for the per-layer
    readers: None where the run counted no applies, its trace is not
    found, or no time went to the scope (a program without it)."""
    applies = ctx["counters"].get("applies")
    sc = for_run(ctx)
    if not applies or sc is None or scope not in sc.seconds:
        return None
    return 1000.0 * sc.seconds[scope] / applies
