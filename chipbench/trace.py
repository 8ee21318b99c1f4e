"""From a profiler trace (``*.xplane.pb``) to numbers. The yardstick: the
program's own reduction (util/profiler.py) may change, this one may not.

A trace holds planes; a device plane (``/device:TPU:n``) holds lines, of which
``XLA Ops`` carries one event per operation that ran on the device and
``XLA Modules`` one per jitted program; host planes hold one line per thread
with the benchmark's own ``cb:`` annotations among the events. All times are
nanoseconds on one clock.

- busy: the union of the op intervals, clipped to the window; idle share is
  1 - busy / window. Over several chips the planes are averaged.
- per-name time: the sum of the durations of the events of one name; a
  program's launches are counted with the share of each that lies inside
  the window (``_launch_shares``), so that steps dispatched ahead, which
  the window's ends cut, count neither as whole steps nor as none.
- gaps: the idle intervals between ops, each named by the innermost ``cb:``
  host span that covers its middle, or by the two programs it lies between.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PREFIX = "cb:"
WINDOW_SPAN = "cb:window"


def newest_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def read_planes(path: str) -> List[dict]:
    """[{name, lines: [{name, events: [(name, start_ns, dur_ns)]}]}] through
    JAX's own reader."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [(e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def merged(intervals):
    """Sorted, disjoint (start, end) intervals covering the same set."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _line(plane: dict, name: str) -> Optional[dict]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def device_planes(planes: List[dict]) -> List[dict]:
    return sorted((p for p in planes
                   if re.match(r"/device:TPU:\d+$", p["name"])),
                  key=lambda p: p["name"])


def host_spans(planes: List[dict]) -> List[Tuple[str, float, float]]:
    """(name, start, end) of every ``cb:`` annotation on any host thread."""
    out = []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for line in p["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(HOST_PREFIX):
                    out.append((name, start, start + dur))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.1 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.1 bf16[8,128]``: the op and what it makes, without layouts
    and operands."""
    m = re.match(r"%?([^\s=]+) = \(?([a-z0-9]+\[[0-9,]*\])?", event_name)
    if not m:
        return event_name[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def module_name(event_name: str) -> str:
    """``jit__decode_paged(1234567)`` -> ``jit__decode_paged``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _clip(events, lo, hi):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def _launch_shares(events, lo, hi) -> List[float]:
    """For each event that ``_clip`` keeps, in its order: how much of one
    launch lies inside the window. The profiler records what was running
    when it started from there only, and what was running when it stopped
    to there only (on the chip: 57.9 ms and 6.5 ms of steps of 107.15 ms,
    the second ending 0.9 ms inside the window), so the length of a line's
    first and last event, and of one that reaches over an end of the
    window, is not the launch's: such an event counts by its length inside
    over the median length of its program's other launches (by its own
    length where there are none), and never as more than one."""
    if not events:
        return []
    first = min(events, key=lambda ev: ev[1])
    last = max(events, key=lambda ev: ev[1] + ev[2])

    def sure(ev):
        return (ev[1] >= lo and ev[1] + ev[2] <= hi
                and ev is not first and ev is not last)

    whole = defaultdict(list)
    for ev in events:
        if sure(ev):
            whole[module_name(ev[0])].append(ev[2])
    shares = []
    for ev in events:
        name, start, dur = ev
        inside = min(start + dur, hi) - max(start, lo)
        if inside <= 0:
            continue
        full = whole.get(module_name(name))
        shares.append(1.0 if sure(ev) else min(
            1.0, inside / (statistics.median(full) if full else dur)))
    return shares


def reduce_trace(planes: List[dict], top: int = 10) -> dict:
    """The numbers the metric readers take from one trace; see module doc."""
    spans = host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no /device:TPU:n plane")
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:   # no annotation: the span of what ran on the devices
        ev = [(s, s + d) for p in devs for ln in p["lines"]
              for _n, s, d in ln["events"]]
        lo, hi = min(s for s, _ in ev), max(e for _, e in ev)
    busy, op_s, mod_s, mod_n = [], defaultdict(float), defaultdict(float), \
        defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    lead_gap: Dict[str, List[float]] = defaultdict(list)
    for p in devs:
        ops_line = _line(p, OPS_LINE) or max(
            p["lines"], key=lambda ln: len(ln["events"]), default=None)
        ops = [(op_name(n), s, e) for n, s, e in
               _clip(ops_line["events"], lo, hi)] if ops_line else []
        covered = merged([(s, e) for _n, s, e in ops])
        busy.append(sum(e - s for s, e in covered))
        for name, s, e in ops:
            op_s[name] += (e - s) / len(devs)
        mods_line = _line(p, MODULES_LINE)
        mods = list(_clip(mods_line["events"], lo, hi)) if mods_line else []
        shares = _launch_shares(mods_line["events"], lo, hi) \
            if mods_line else []
        for (name, s, e), share in zip(mods, shares):
            mod_s[module_name(name)] += (e - s) / len(devs)
            mod_n[module_name(name)] += share
        prev_end = None
        for name, s, e in sorted(mods, key=lambda m: m[1]):
            if prev_end is not None:
                lead_gap[module_name(name)].append(max(0.0, s - prev_end)
                                                   * 1e-9)
            prev_end = e
        for name, dur in _name_gaps(covered, lo, hi, mods, spans):
            gaps[name] += dur / len(devs)
    ns = 1e-9
    span_s: Dict[str, List[float]] = defaultdict(list)
    for name, s, e in spans:
        if s >= lo and e <= hi:
            span_s[name].append((e - s) * ns)
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "chips": len(devs),
        "op_s": {k: v * ns for k, v in op_s.items()},
        "module_s": {k: v * ns for k, v in mod_s.items()},
        "module_n": {k: v / len(devs) for k, v in mod_n.items()},
        "module_lead_gap_s": dict(lead_gap),
        "span_s": dict(span_s),
        "breakdown": {
            "device_ops": [[k, v * ns] for k, v in sorted(
                op_s.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v * ns] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]],
        },
    }


def _name_gaps(covered, lo, hi, mods, spans):
    """Yield (name, length) for every idle interval of one device: inside a
    program it belongs to the program; between programs to the innermost
    ``cb:`` host span over its middle, else to the pair of programs."""
    import bisect

    edges = [lo] + [t for s, e in covered for t in (s, e)] + [hi]
    mods = sorted(mods, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    spans = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        k = bisect.bisect_right(starts, mid) - 1
        if k >= 0 and mods[k][2] >= mid:
            yield "within:" + module_name(mods[k][0]), g1 - g0
            continue
        inside = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        if inside:
            yield "in:" + min(inside)[1][len(HOST_PREFIX):], g1 - g0
            continue
        prev = module_name(mods[k][0]) if k >= 0 else "start"
        nxt = module_name(mods[k + 1][0]) if k + 1 < len(mods) else "end"
        yield f"between:{prev}>{nxt}", g1 - g0


def reduce_logdir(logdir: str) -> dict:
    return reduce_trace(read_planes(newest_xplane(logdir)))
