"""From a JAX profiler trace to the events the metric readers use.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each chip is a plane named ``/device:TPU:<i>``; its ``XLA Ops`` line holds
one event per operation that ran, named as the compiled program names it.
Host threads are lines of the ``/host:CPU`` plane; the harness marks its own
work there with ``jax.profiler.TraceAnnotation`` (``bench.window`` around
the measured window, ``bench.solve``, ``bench.submit``, ``bench.wait``).
Times are nanoseconds on one clock for host and devices.

On a v5e an op's event is named by its whole HLO instruction
(``%countsketch_apply.2 = f32[4096,1000]{...} custom-call(...),
custom_call_target="tpu_custom_call", ...``); :func:`short_name` keeps the
instruction's own name and, for a custom call, its target
(``countsketch_apply.2 [tpu_custom_call]``), so that an operand's name never
matches.  Control-flow ops (``while``, ``conditional``, ``call``) span the
ops they run; they count toward busy time but not among the top ops.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_CONTROL = re.compile(r"^(while|conditional|call)(\.|$)")


def short_name(text: str) -> str:
    """``name [target]`` of an HLO instruction's text, or the text itself
    where it is no instruction."""
    head, sep, _ = text.partition(" = ")
    if not sep:
        return text
    name = head.strip().lstrip("%")
    target = _TARGET.search(text)
    return f"{name} [{target.group(1)}]" if target else name


def is_control(name: str) -> bool:
    return bool(_CONTROL.match(name))


@dataclasses.dataclass
class Op:
    name: str
    start: float  # ns
    end: float  # ns
    category: str = ""


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]  # ns, the bench.window annotation
    ops: dict[int, list[Op]]  # device index -> its ops inside the window
    host: list[Op]  # host events overlapping the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, device: int) -> float:
        return _union(self.ops.get(device, ())) * 1e-9

    def mean_busy_s(self) -> float:
        if not self.ops:
            return 0.0
        return sum(self.busy_s(d) for d in self.ops) / len(self.ops)

    def op_seconds(self, match) -> float:
        """Seconds of the ops whose name ``match`` accepts, summed on each
        chip and averaged over the chips."""
        if not self.ops:
            return 0.0
        total = sum(o.end - o.start for ops in self.ops.values()
                    for o in ops if match(o.name))
        return total * 1e-9 / len(self.ops)

    def count(self, match) -> int:
        return sum(1 for ops in self.ops.values() for o in ops
                   if match(o.name))


def _union(ops) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for o in sorted(ops, key=lambda o: o.start):
        if cur_e is None or o.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = o.start, o.end
        else:
            cur_e = max(cur_e, o.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` and keep what lies inside ``bench.window``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, window = [], None
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                host.append(Op(ev.name, ev.start_ns, ev.end_ns, line.name))
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.end_ns)
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    lo, hi = window
    ops: dict[int, list[Op]] = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        try:
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
        except ValueError:
            continue
        kept = ops.setdefault(dev, [])
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
                if e > s:
                    kept.append(Op(short_name(ev.name), s, e,
                                   str(_stat(ev, "hlo_category") or "")))
    host = [h for h in host if h.end > lo and h.start < hi]
    return Trace(window=window, ops=ops, host=host)


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` op names that took the most device time, in seconds per
    chip."""
    tally: dict[str, float] = {}
    for ops in trace.ops.values():
        for o in ops:
            if is_control(o.name):
                continue
            tally[o.name] = tally.get(o.name, 0.0) + (o.end - o.start)
    chips = max(len(trace.ops), 1)
    best = sorted(tally.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9 / chips] for name, ns in best]


def idle_gaps(trace: Trace, device: int | None = None, k: int = 10) -> list[list]:
    """The ``k`` longest stretches of the window with no op on ``device``
    (the first chip by default), each named by the host work that overlaps
    it most: a ``bench.*`` annotation where one does, else the longest
    overlapping host event, else ``host idle``."""
    if not trace.ops:
        return []
    if device is None:
        device = min(trace.ops)
    lo, hi = trace.window
    gaps, cur = [], lo
    for o in sorted(trace.ops[device], key=lambda o: o.start):
        if o.start > cur:
            gaps.append((cur, o.start))
        cur = max(cur, o.end)
    if hi > cur:
        gaps.append((cur, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    out = []
    for s, e in gaps:
        out.append([_host_activity(trace.host, s, e), (e - s) * 1e-9])
    return out


def _host_activity(host, s, e) -> str:
    best, best_ov, mine, mine_ov = "host idle", 0.0, None, 0.0
    for h in host:
        if h.name == WINDOW:
            continue
        ov = min(h.end, e) - max(h.start, s)
        if ov <= 0:
            continue
        if h.name.startswith("bench.") and ov > mine_ov:
            mine, mine_ov = h.name, ov
        if ov > best_ov:
            best, best_ov = h.name, ov
    return mine if mine is not None else best


def to_dict(trace: Trace, seconds: float | None = None) -> dict:
    """A compact, JSON-ready copy of ``trace``, cut to its first
    ``seconds`` where given (a fixture for the readers' tests)."""
    lo, hi = trace.window
    if seconds is not None:
        hi = min(hi, lo + seconds * 1e9)
    names: dict[str, int] = {}

    def rows(ops):
        out = []
        for o in ops:
            if o.end > lo and o.start < hi:
                idx = names.setdefault(o.name, len(names))
                out.append([idx, int(max(o.start, lo) - lo),
                            int(min(o.end, hi) - lo)])
        return out

    return {
        "window_ns": int(hi - lo),
        "ops": {str(d): rows(ops) for d, ops in trace.ops.items()},
        "host": rows([h for h in trace.host if h.name.startswith("bench.")]),
        "names": sorted(names, key=names.get),
    }


def from_dict(d: dict) -> Trace:
    names = d["names"]

    def ops(rows):
        return [Op(names[i], float(s), float(e)) for i, s, e in rows]

    return Trace(window=(0.0, float(d["window_ns"])),
                 ops={int(k): ops(v) for k, v in d["ops"].items()},
                 host=ops(d["host"]))
