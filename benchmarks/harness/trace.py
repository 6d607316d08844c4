"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What it takes from the trace, with ``jax.profiler.ProfileData``:

- the traced window: the host annotation ``bench.window`` that the
  harness wraps around the measured window;
- per device plane (``/device:TPU:<n>``), the ``XLA Ops`` events (every
  operation that ran on the device) and the ``XLA Modules`` events (one
  per executed program, named after the jitted function);
- the host planes' events, to say what the host was doing while a
  device sat idle.

What it gives (all clipped to the window): the union of device busy
intervals per device; device time per module and per op name; the
collective ops' time not overlapped by other device work; and the
longest idle gaps, each labelled by the host event that overlapped it
most.
"""

from __future__ import annotations

import bisect
import collections
import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
_COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all|send|recv)",
                         re.IGNORECASE)
# Host events that say nothing about what the host was doing in a gap.
_UNINFORMATIVE = re.compile(r"^(bench\.window|bench\.wait)$")


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)        # (name, start, end) ns
    modules: list = field(default_factory=list)


@dataclass
class Reduction:
    window_s: float
    devices: list                 # device plane names
    busy_s: float                 # mean over devices
    module_s: dict                # program -> device seconds (summed)
    op_s: dict                    # "program/op" -> device seconds (summed)
    collective_exposed_s: float   # mean over devices
    idle_gaps: list               # [(label, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, pattern: str) -> float:
        """Device seconds, summed over devices, of the modules whose
        name matches the regular expression ``pattern``."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.module_s.items() if rx.search(k))

    def top_ops(self, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns
                                                  + ev.duration_ns)


def module_name(name: str) -> str:
    """``jit__fused_call(1234)`` -> ``jit__fused_call``: one name for
    every compiled shape of a program."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """``%_fused_call.1 = f32[...] custom-call(...)`` -> ``_fused_call.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _owner(modules, starts, s: float) -> str:
    """The program (module) running on the device at ``s``."""
    i = bisect.bisect_right(starts, s) - 1
    if i >= 0 and modules[i][1] <= s < modules[i][2]:
        return module_name(modules[i][0])
    return "?"


def reduce(path: str, *, max_gaps: int = 10) -> Reduction:
    """Reduce the trace at ``path`` over its ``bench.window``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: list[Device] = []
    host: list[tuple[str, float, float]] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops.extend(_events(line))
                elif line.name == "XLA Modules":
                    dev.modules.extend(_events(line))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW:
                        window = (s, e)
                    elif e > s:
                        host.append((name, s, e))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} host annotation")
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    w0, w1 = window
    module_s: dict = collections.defaultdict(float)
    op_s: dict = collections.defaultdict(float)
    busy, exposed = [], []
    gaps_dev0 = []
    for i, dev in enumerate(devices):
        spans, coll, rest = [], [], []
        mods = sorted(dev.modules, key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, e in dev.ops:
            owner = _owner(mods, starts, s)
            s, e = _clip(s, e, w0, w1)
            if e <= s:
                continue
            spans.append((s, e))
            short = op_name(name)
            op_s[f"{owner}/{short}"] += (e - s) / 1e9
            (coll if _COLLECTIVE.match(short) else rest).append((s, e))
        for name, s, e in mods:
            s, e = _clip(s, e, w0, w1)
            if e > s:
                module_s[module_name(name)] += (e - s) / 1e9
        u = union(spans)
        busy.append(measure(u) / 1e9)
        exposed.append(measure(subtract(union(coll), union(rest))) / 1e9)
        if i == 0:
            gaps_dev0 = subtract([(w0, w1)], u)
    longest = sorted(gaps_dev0, key=lambda g: g[0] - g[1])[:max_gaps]
    return Reduction(
        window_s=(w1 - w0) / 1e9,
        devices=[d.name for d in devices],
        busy_s=sum(busy) / len(busy),
        module_s=dict(module_s), op_s=dict(op_s),
        collective_exposed_s=sum(exposed) / len(exposed),
        idle_gaps=[[_label(host, s, e), (e - s) / 1e9] for s, e in longest])


def _label(host, s, e) -> str:
    """The host event that overlaps ``[s, e)`` most, ignoring the
    harness's window and wait annotations."""
    best, best_ov = "no host event", 0.0
    for name, hs, he in host:
        if he <= s or hs >= e or _UNINFORMATIVE.match(name):
            continue
        ov = min(he, e) - max(hs, s)
        if ov > best_ov:
            best, best_ov = name, ov
    return best
