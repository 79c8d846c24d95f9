"""Reduction of a profiler trace to the numbers the readers use.

`load_xspace` turns the trace that the profiler's session hands over into a
plain structure (lists of `[name, start_s, seconds]` per device line
and the host's sync mark); everything after it works on that structure,
so the tests check the arithmetic on a small recorded trace
(`benchmarks/tests/data/`).

- module time per `stage_*`: the device seconds of each XLA module,
  keyed by the stage function it was jitted from;
- busy: the union of the intervals in which a module ran on the
  device, averaged over the chips used;
- idle gaps: the longest intervals inside the traced window in which
  nothing ran, each named by the host span it mostly falls in.
"""

import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SYNC_MARK = "bench_sync"
MODULE_LINE = "XLA Modules"
SHORT_GAP_S = 1e-4
_STAGE = re.compile(r"(stage_[a-z0-9_]+?|_pk_validate_kernel)(?:[.(]|$)")

Event = Tuple[str, float, float]      # name, start_s, seconds


def load_xspace(xspace: bytes) -> dict:
    """From the profiler session's serialized trace:
    {"devices": {plane name: {"XLA Modules": [Event]}}, "sync_s":
    float or None}: times in seconds on the trace's own clock.  Only
    the module line is read: the op line holds some 2.4 million events a
    dispatch (one per iteration of every loop) and takes a minute to
    walk, and the union of its intervals came to the sum of the
    modules' seconds within 0.03 % (PR 25: 0.937849 s against
    0.938070 s), so a module's interval stands for its ops."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(xspace)
    out = {"devices": {}, "sync_s": None}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    lines[line.name] = [
                        (ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9)
                        for ev in line.events]
            if lines:
                out["devices"][plane.name] = lines
        elif out["sync_s"] is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SYNC_MARK:
                        out["sync_s"] = ev.start_ns / 1e9
                        break
                if out["sync_s"] is not None:
                    break
    return out


def stage_of(module_name: str) -> str:
    """`jit_stage_h2c(123)` -> `stage_h2c`; other modules keep their
    name without the run id."""
    m = _STAGE.search(module_name)
    if m:
        return m.group(1)
    return re.sub(r"\(\d+\)$", "", module_name)


def module_seconds(trace: dict) -> Dict[str, float]:
    """Device seconds per module, summed over the chips."""
    out: Dict[str, float] = {}
    for lines in trace["devices"].values():
        for name, _start, secs in lines.get(MODULE_LINE, ()):
            key = stage_of(name)
            out[key] = out.get(key, 0.0) + secs
    return out


def union(intervals) -> Tuple[np.ndarray, np.ndarray]:
    """The merged intervals, in order: (starts, ends)."""
    arr = np.asarray(intervals, dtype=np.float64).reshape(-1, 2)
    if len(arr) == 0:
        return np.zeros(0), np.zeros(0)
    arr = arr[np.argsort(arr[:, 0], kind="stable")]
    ends = np.maximum.accumulate(arr[:, 1])
    # a new interval begins where a start lies past every earlier end
    new = np.ones(len(arr), dtype=bool)
    new[1:] = arr[1:, 0] > ends[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(arr) - 1)
    return arr[first, 0], ends[last]


def _busy_intervals(lines: dict, lo: float, hi: float):
    mods = lines.get(MODULE_LINE) or ()
    start = np.asarray([s for _n, s, _d in mods], dtype=np.float64)
    end = start + np.asarray([d for _n, _s, d in mods], dtype=np.float64)
    keep = (end > lo) & (start < hi)
    clipped = np.stack([np.maximum(start[keep], lo),
                        np.minimum(end[keep], hi)], axis=1)
    return union(clipped)


def busy_seconds(trace: dict, lo: float, hi: float) -> Optional[float]:
    """Seconds inside [lo, hi] in which an operation ran, averaged over
    the chips in the trace."""
    per_chip = []
    for lines in trace["devices"].values():
        starts, ends = _busy_intervals(lines, lo, hi)
        per_chip.append(float((ends - starts).sum()))
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip)


def traced_span(trace: dict) -> Optional[Tuple[float, float]]:
    """First start and last end of any device event."""
    starts, ends = [], []
    for lines in trace["devices"].values():
        for _n, s, d in lines.get(MODULE_LINE, ()):
            starts.append(s)
            ends.append(s + d)
    if not starts:
        return None
    return min(starts), max(ends)


def idle_gaps(trace: dict, lo: float, hi: float,
              host_spans: Sequence[Event], top: int = 10
              ) -> List[List[object]]:
    """The longest idle intervals of the first chip inside [lo, hi],
    merged by the host span each mostly falls in: [[name, seconds]].
    `host_spans` are on the trace's clock."""
    lines = next(iter(trace["devices"].values()), None)
    if lines is None:
        return []
    starts, ends = _busy_intervals(lines, lo, hi)
    g0s = np.concatenate([[lo], ends])
    g1s = np.concatenate([starts, [hi]])
    size = g1s - g0s
    # between the programs of one dispatch the device pauses for
    # microseconds: one name for all of those
    small = (size > 0) & (size < SHORT_GAP_S)
    named: Dict[str, float] = {}
    if small.any():
        named["between_modules"] = float(size[small].sum())
    for g0, g1 in zip(g0s[size >= SHORT_GAP_S].tolist(),
                      g1s[size >= SHORT_GAP_S].tolist()):
        cover: Dict[str, float] = {}
        for stage, s, d in host_spans:
            ov = min(g1, s + d) - max(g0, s)
            if ov > 0:
                cover[stage] = cover.get(stage, 0.0) + ov
        if cover:
            name = max(cover, key=cover.get)
            # spans of one batch overlap each other; a gap is named by
            # the span that covers most of it, if that is half or more
            if cover[name] < 0.5 * (g1 - g0):
                name = "between_spans"
        else:
            name = "waiting_for_tasks"
        named[name] = named.get(name, 0.0) + (g1 - g0)
    ranked = sorted(named.items(), key=lambda kv: -kv[1])[:top]
    return [[name, secs] for name, secs in ranked]


def top_modules(trace: dict, top: int = 10) -> List[List[object]]:
    ranked = sorted(module_seconds(trace).items(), key=lambda kv: -kv[1])
    return [[name, secs] for name, secs in ranked[:top]]
