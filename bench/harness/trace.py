"""Reading the profiler's trace: device busy time, idle gaps and the host
spans they fall in, and device time per kind of instruction (a Pallas call's kind is its
kernel's name).

The reduction works on plain ``(name, start_ns, end_ns)`` tuples, so a
test can feed it a synthetic trace; ``load`` builds them from the
``.xplane.pb`` file that ``jax.profiler`` writes. On a TPU the device
plane is ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per
HLO instruction executed, named by the instruction's text (a Pallas call
reads ``%<kernel name>.<id> = <output shape> custom-call(...)``), and
its ``XLA Modules`` line one event per executable run. Host spans are the
``TraceAnnotation`` events on the ``/host:CPU`` plane.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]       # device id -> XLA Ops events
    modules: Dict[int, List[Event]]   # device id -> XLA Modules events
    host: List[Event]                 # host spans of the benchmark


def load(log_dir: str, host_prefix: str = "bench.") -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                dest[dev] = [(e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                             for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        host.append((e.name, int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)))
    return Trace(ops=ops, modules=modules, host=host)


def clip(events: Sequence[Event], t0: int, t1: int) -> List[Event]:
    """Events cut to [t0, t1]; those wholly outside are dropped."""
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events
            if e > t0 and s < t1]


def union(events: Sequence[Event]) -> List[Tuple[int, int]]:
    """Merged busy intervals of (possibly nested or overlapping) events."""
    out: List[List[int]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event], t0: int, t1: int) -> int:
    return sum(e - s for s, e in union(clip(events, t0, t1)))


def gaps(events: Sequence[Event], t0: int, t1: int) -> List[Tuple[int, int]]:
    """Idle intervals of the device inside [t0, t1]."""
    out, cur = [], t0
    for s, e in union(clip(events, t0, t1)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def attribute(idle: Sequence[Tuple[int, int]], host: Sequence[Event],
              min_ns: int = 20_000) -> Dict[str, int]:
    """Idle nanoseconds by what the host was doing. Gaps shorter than
    ``min_ns`` (between the ops of one executable) are summed apart;
    each piece of a longer gap goes to the innermost (shortest) host span
    that covers it, and to ``"no span"`` where none does."""
    small = f"gaps under {min_ns // 1000} us"
    out: Dict[str, int] = {}
    spans = sorted(host, key=lambda x: x[2] - x[1])
    for g in idle:
        if g[1] - g[0] < min_ns:
            out[small] = out.get(small, 0) + (g[1] - g[0])
            continue
        near = [sp for sp in spans if sp[2] > g[0] and sp[1] < g[1]]
        cuts = sorted({g[0], g[1]} | {t for _, s, e in near
                                       for t in (s, e) if g[0] < t < g[1]})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) // 2
            name = next((n for n, s, e in near if s <= mid < e), "no span")
            out[name] = out.get(name, 0) + (b - a)
    return out


def op_kind(name: str) -> str:
    """An ``XLA Ops`` event's instruction name without its id:
    ``%fusion.12 = ...`` -> ``fusion``."""
    m = re.match(r"%([A-Za-z0-9_\-]+?)(?:\.\d+)? = ", name)
    return m.group(1) if m else name.split(" ")[0][:64]


CONTAINERS = ("while", "conditional", "call")


def kind_time(ops: Sequence[Event], t0: int, t1: int
              ) -> Dict[str, Tuple[int, int]]:
    """(calls, device ns) per instruction kind, over the ops wholly
    inside [t0, t1]. A Pallas call's kind is its kernel's name."""
    out: Dict[str, Tuple[int, int]] = {}
    for n, s, e in ops:
        if s < t0 or e > t1:
            continue
        k = op_kind(n)
        c, d = out.get(k, (0, 0))
        out[k] = (c + 1, d + (e - s))
    return out


def top_ops(ops: Sequence[Event], t0: int, t1: int, k: int = 10
            ) -> List[Tuple[str, float]]:
    """Device seconds by instruction kind, leaving out control-flow
    containers (they hold the other ops), largest first."""
    agg: Dict[str, int] = {}
    for n, s, e in clip(ops, t0, t1):
        kind = op_kind(n)
        if kind in CONTAINERS:
            continue
        agg[kind] = agg.get(kind, 0) + (e - s)
    return [(n, d / 1e9) for n, d in
            sorted(agg.items(), key=lambda kv: -kv[1])[:k]]
