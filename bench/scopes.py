"""The program's own names in the run's profile: the name scopes of the
device ops and the ``repro.obs`` spans on the host.

Inside its jitted programs the solver names its layers with
``jax.named_scope``: ``refine`` (the heavy-ball loop of ``lstsq``),
``lsqr`` (the service's batched LSQR) and ``certify`` (the service's
blocked certificate).  XLA keeps the scope path in each op's ``op_name``
metadata; a v5e profile carries it in the ``tf_op`` stat of the op's event
metadata, as in
``jit(iterative_sketching)/jit(heavy_ball_refine)/refine/while/body/dot_general:``.
A scope counts only as a whole component of that path, so neither
``jit(lsqr)`` nor a ``certify_block`` function is ``lsqr`` or ``certify``.

``bench.trace`` keeps only the ops' names, and ``jax.profiler.ProfileData``
gives no event metadata's stats, so this module reads the device planes of
the run's ``.xplane.pb`` itself: the newest profile under
``.bench_out/trace/<cell>-*/``, which the harness removes only after the
readers have run.  It is parsed once per file and shared by every reader,
and used only if its ``bench.window`` is the one ``run.trace`` was read
from.  Ops are joined to their metadata by id, not by name: two programs
can hold ops of the same HLO text under different scopes.

While ``repro.obs`` traces, each of its spans is also a host event of the
profile, named as the span is (``serve.dispatch.session``, ...); they are
in ``run.trace.host``.  Where the program has no scopes or spans, as before
they were added, the readers find nothing and read ``None``.
"""
from __future__ import annotations

import dataclasses
import glob
import os

from bench import trace as trace_lib

DISPATCH = "serve.dispatch."
SESSION_BATCH = "serve.dispatch.session"
OP_PATH_STAT = "tf_op"


def scope_path(tf_op: str) -> tuple[str, ...]:
    """The components of an op's path as ``tf_op`` holds it (``name:type``,
    the type empty for an XLA op)."""
    name = tf_op.partition(":")[0]
    return tuple(name.split("/")) if name else ()


# The fields of tsl/profiler/protobuf/xplane.proto read here: XSpace.planes
# 1; XPlane.name 2, .lines 3, .event_metadata 4, .stat_metadata 5 (maps:
# key 1, value 2); XLine.name 2, .timestamp_ns 3, .events 4; XEvent
# .metadata_id 1, .offset_ps 2, .duration_ps 3; XEventMetadata.id 1, .name 2,
# .stats 5; XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .str_value 5,
# .ref_value 7 (the id of a stat metadata whose name is the value).


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of the message in ``buf[lo:hi]``; the value of
    a length-delimited field is its (start, end), of a fixed-width one
    None."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"xplane: unknown wire type {wire}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, fields, number: int):
    for f, entry in fields:
        if f == number:
            yield next((v for g, v in _fields(buf, *entry) if g == 2), (0, 0))


def _op_paths(buf, fields) -> dict:
    """{event metadata id: (op name, scope path)} of one plane."""
    stat_names = {}
    for meta in _map_values(buf, fields, 5):
        sid, name = 0, ""
        for f, v in _fields(buf, *meta):
            if f == 1:
                sid = v
            elif f == 2:
                name = _text(buf, v)
        stat_names[sid] = name
    out = {}
    for meta in _map_values(buf, fields, 4):
        mid, name, tf_op = 0, "", ""
        for f, v in _fields(buf, *meta):
            if f == 1:
                mid = v
            elif f == 2:
                name = _text(buf, v)
            elif f == 5:
                stat = dict(_fields(buf, *v))
                if stat_names.get(stat.get(1)) != OP_PATH_STAT:
                    continue
                if 5 in stat:
                    tf_op = _text(buf, stat[5])
                elif 7 in stat:
                    tf_op = stat_names.get(stat[7], "")
        out[mid] = (trace_lib.short_name(name), scope_path(tf_op))
    return out


@dataclasses.dataclass
class ScopedOp:
    path: tuple[str, ...]
    start: float  # ns
    end: float  # ns


@dataclasses.dataclass
class Scoped:
    window: tuple[float, float]  # ns, the bench.window annotation
    ops: dict[int, list[ScopedOp]]  # device -> its non-control ops

    def seconds(self, scope: str) -> float:
        """Seconds of the ops under ``scope``, summed on each chip and
        averaged over the chips."""
        if not self.ops:
            return 0.0
        total = sum(o.end - o.start for ops in self.ops.values()
                    for o in ops if scope in o.path)
        return total * 1e-9 / len(self.ops)

    def count(self, scope: str) -> int:
        return sum(1 for ops in self.ops.values() for o in ops
                   if scope in o.path)


def _window(path: str) -> tuple[float, float]:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_lib.WINDOW:
                        return ev.start_ns, ev.end_ns
    raise ValueError(f"no {trace_lib.WINDOW!r} annotation in {path}")


def load(path: str) -> Scoped:
    """The device ops of an ``.xplane.pb`` inside its ``bench.window``,
    each with its scope path; control-flow ops, which span the ops they
    run, are left out so that no time counts twice."""
    lo, hi = window = _window(path)
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    ops: dict[int, list[ScopedOp]] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        fields = list(_fields(buf, *plane))
        name = next((_text(buf, v) for f, v in fields if f == 2), "")
        if not name.startswith(trace_lib.DEVICE_PREFIX):
            continue
        try:
            dev = int(name[len(trace_lib.DEVICE_PREFIX):].split()[0])
        except ValueError:
            continue
        paths = _op_paths(buf, fields)
        kept = ops.setdefault(dev, [])
        for f, line in fields:
            if f != 3:
                continue
            line_fields = list(_fields(buf, *line))
            if not any(g == 2 and _text(buf, v) == trace_lib.OPS_LINE
                       for g, v in line_fields):
                continue
            t0 = next((v for g, v in line_fields if g == 3), 0)
            for g, event in line_fields:
                if g != 4:
                    continue
                ev = dict(_fields(buf, *event))
                op, scope = paths.get(ev.get(1, 0), ("", ()))
                s = t0 + ev.get(2, 0) / 1000
                e = s + ev.get(3, 0) / 1000
                s, e = max(s, lo), min(e, hi)
                if e > s and not trace_lib.is_control(op):
                    kept.append(ScopedOp(scope, s, e))
    return Scoped(window=window, ops=ops)


_parsed: dict = {}  # the last file read: {"key": (path, mtime, size), ...}


def _newest_profile(trace_root, cell: str) -> str | None:
    paths = glob.glob(os.path.join(str(trace_root), f"{cell}-*", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def of_run(run) -> Scoped | None:
    """The scoped ops of ``run``'s profile, or None in an untraced run, or
    where no profile of the run is on disk."""
    from bench import harness

    if run.trace is None:
        return None
    path = _newest_profile(harness.OUT_DIR / "trace", run.cell)
    if path is None:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if _parsed.get("key") != key:
        _parsed.clear()
        _parsed.update(key=key, scoped=load(path))
    scoped = _parsed["scoped"]
    return scoped if tuple(scoped.window) == tuple(run.trace.window) else None


def session_batches(trace) -> int:
    """Batches the service dispatched in the window: its
    ``serve.dispatch.session`` spans."""
    return sum(1 for h in trace.host if h.name == SESSION_BATCH)
