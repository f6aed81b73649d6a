"""From a profiler trace to device busy time, kernel time and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData``.  What names what is data, in
``bench/trace_names.json``: the prefix of the device planes, the lines on
them that hold one event per device operation, the event names of each
kernel, and the host annotation that marks the measured window.  The
idle gaps are attributed to the host annotations the caller names: the
benchmark's own, whatever a driver calls them.
"""
from __future__ import annotations

import bisect
import json
from collections import defaultdict
from pathlib import Path

NAMES_FILE = Path(__file__).resolve().parent.parent / "trace_names.json"


def load_names() -> dict:
    return json.loads(NAMES_FILE.read_text())


def find_xplane(trace_dir: Path) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def kernel_of(name: str, kernels: dict[str, list[str]]) -> str | None:
    for kernel, patterns in kernels.items():
        if any(p in name for p in patterns):
            return kernel
    return None


def op_label(name: str, kernel: str | None) -> str:
    """A kernel's events by kernel and output shape (``chunker
    u8[64,4992]``); any other operation by the start of its name."""
    if kernel is None:
        return name[:100]
    shape = name.split("=", 1)[-1].strip().split("{", 1)[0]
    return f"{kernel} {shape}"


def reduce_events(device: dict[str, list[tuple[str, int, int]]],
                  host: list[tuple[str, int, int]], names: dict,
                  activities) -> dict:
    """``device``: plane name -> [(op name, start_ns, end_ns)];
    ``host``: [(annotation, start_ns, end_ns)]; ``activities``: the
    annotations idle gaps are attributed to.  Everything is clipped to
    the window annotation.  Returns seconds."""
    windows = [(s, e) for n, s, e in host if n == names["window"]]
    if not windows:
        raise ValueError(f"no {names['window']!r} annotation in the trace")
    w0, w1 = windows[0]
    activities = set(activities)
    spans = sorted((s, e, n) for n, s, e in host
                   if n in activities and e > w0 and s < w1)
    starts = [s for s, _, _ in spans]
    busy, kernel_s = [], defaultdict(float)
    op_s: dict[str, float] = defaultdict(float)
    idle: dict[str, float] = defaultdict(float)
    for plane, events in device.items():
        clipped = [(max(s, w0), min(e, w1), n) for n, s, e in events
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            k = kernel_of(n, names["kernels"])
            if k is not None:
                kernel_s[k] += (e - s) / 1e9
            op_s[op_label(n, k)] += (e - s) / 1e9
        union = merge([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in union) / 1e9)
        # the gaps between device operations, by what the host was doing
        edges = [w0] + [x for iv in union for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            covered = 0
            i = max(0, bisect.bisect_right(starts, g0) - 1)
            while i < len(spans) and spans[i][0] < g1:
                s, e, n = spans[i]
                part = min(e, g1) - max(s, g0)
                if part > 0:
                    idle[n] += part / 1e9
                    covered += part
                i += 1
            idle[names["unannotated"]] += max(0, g1 - g0 - covered) / 1e9
    n_dev = max(1, len(device))
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy) / n_dev,
            "devices": len(device),
            "kernel_s": {k: v / n_dev for k, v in kernel_s.items()},
            "device_ops": [[n, v / n_dev] for n, v in top],
            "idle_gaps": [[n, v / n_dev] for n, v in gaps]}


def read_trace(path: Path, names: dict, chips: int, activities) -> dict:
    """Reduce one recorded trace: the first ``chips`` device planes, idle
    gaps by the host annotations named in ``activities``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device: dict[str, list[tuple[str, int, int]]] = {}
    host: list[tuple[str, int, int]] = []
    keep = set(activities) | {names["window"]}
    planes = sorted((p for p in pd.planes
                     if p.name.startswith(names["device_plane"])),
                    key=lambda p: p.name)[:chips]
    for plane in planes:
        device[plane.name] = [
            (ev.name, int(ev.start_ns), int(ev.end_ns))
            for line in plane.lines if line.name in names["op_lines"]
            for ev in line.events]
    for plane in pd.planes:
        if plane.name.startswith(names["host_plane"]):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.end_ns)))
    return reduce_events(device, host, names, activities)
