"""From a profiler trace to the program's own spans: self time, device
idle time by span, and the host time of kernel launches.

The program mirrors its ``obs`` spans into the profiler's trace where the
tracing process sets ``obs.annotate_with`` around
``jax.profiler.start_trace`` / ``stop_trace``, so they sit on the host
plane on the clock the device operations use.  ``bench/run.py`` sets
``mirrored(jax.profiler.TraceAnnotation)``, which writes each span under
its own name behind the mark of ``bench/program_spans.json``.  This reads
the same ``.xplane.pb`` as ``trace.py``: every marked host event, whatever
the program named it, clipped to the window annotation and nested per
thread line; the benchmark's annotations and the runtime's own events
carry no mark.  A program that writes no spans gives empty results, never
an error.
"""
from __future__ import annotations

import json
from collections import defaultdict
from pathlib import Path

from .trace import kernel_of, merge

SPANS_FILE = Path(__file__).resolve().parent.parent / "program_spans.json"


def load_spans() -> dict:
    return json.loads(SPANS_FILE.read_text())


def mirrored(factory, program_names: dict):
    """The factory for ``obs.annotate_with``: each program span enters
    ``factory`` under its name behind the mark."""
    mark = program_names["mark"]
    return lambda name: factory(mark + name)


def pieces(a: list[tuple[int, int]], b: list[tuple[int, int]]):
    """The overlaps of two sorted lists of disjoint intervals, as
    ``(start, end, index in a, index in b)``."""
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            yield s, e, i, j
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1


def nest(events: list[tuple[int, int, str]]) -> list[list]:
    """Spans of one thread as ``[start, end, name, parent]``, ``parent``
    the index of the innermost span enclosing it (-1 for none).  A span
    that outlasts its parent is cut at the parent's end."""
    out: list[list] = []
    stack: list[int] = []
    for s, e, n in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        parent = stack[-1] if stack else -1
        if parent >= 0:
            e = min(e, out[parent][1])
        out.append([s, e, n, parent])
        stack.append(len(out) - 1)
    return out


def reduce_program(device: dict[str, list[tuple[str, int, int]]],
                   host: dict[str, list[tuple[str, int, int]]],
                   trace_names: dict, program_names: dict) -> dict:
    """``device``: plane name -> [(op name, start_ns, end_ns)]; ``host``:
    thread line -> [(event name, start_ns, end_ns)], the window
    annotation among them; a program span's name carries the mark, and
    the results name it without.  Returns seconds:

    - ``spans``: per span name its ``count``, ``total_s``, ``self_s``
      (its time less the union of its direct child spans) and
      ``inside``, the count of its spans under each enclosing name;
    - ``idle``: device-idle time by the innermost span open, and
      ``none`` where no span was open, largest first;
    - ``kernel_host_s``: time inside kernel spans with no device
      operation running; ``kernel_device_s``: per kernel, device time of
      its operations inside its own spans.

    Device times are per device, averaged over the devices."""
    windows = [(s, e) for evs in host.values() for n, s, e in evs
               if n == trace_names["window"]]
    if not windows:
        raise ValueError(f"no {trace_names['window']!r} annotation")
    w0, w1 = min(windows)
    mark = program_names["mark"]
    prefix = program_names["kernel_prefix"]
    stats: dict[str, dict] = {}
    segments: list[list[tuple[int, int, str]]] = []   # self time, by line
    kernel_iv: dict[str, list[tuple[int, int]]] = defaultdict(list)
    for evs in host.values():
        tree = nest([(max(s, w0), min(e, w1), n[len(mark):])
                     for n, s, e in evs
                     if n.startswith(mark) and e > w0 and s < w1])
        children: dict[int, list[int]] = defaultdict(list)
        for i, (_, _, _, parent) in enumerate(tree):
            if parent >= 0:
                children[parent].append(i)
        line: list[tuple[int, int, str]] = []
        for i, (s, e, n, parent) in enumerate(tree):
            st = stats.setdefault(n, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0, "inside": {}})
            st["count"] += 1
            st["total_s"] += (e - s) / 1e9
            edge = s
            for c in children[i]:
                if tree[c][0] > edge:
                    line.append((edge, tree[c][0], n))
                edge = max(edge, tree[c][1])
            if e > edge:
                line.append((edge, e, n))
            above = set()
            while parent >= 0:
                above.add(tree[parent][2])
                parent = tree[parent][3]
            for a in above:
                st["inside"][a] = st["inside"].get(a, 0) + 1
            if n.startswith(prefix):
                kernel_iv[n[len(prefix):]].append((s, e))
        for s, e, n in line:
            stats[n]["self_s"] += (e - s) / 1e9
        segments.append(sorted(line))
    idle: dict[str, float] = defaultdict(float)
    kernel_host = 0.0
    kernel_dev: dict[str, float] = defaultdict(float)
    launches = merge([iv for ivs in kernel_iv.values() for iv in ivs])
    for events in device.values():
        clipped = [(max(s, w0), min(e, w1), n) for n, s, e in events
                   if e > w0 and s < w1]
        busy = merge([(s, e) for s, e, _ in clipped])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                if g1 > g0]
        covered = []
        for line in segments:
            spans_of_line = [(s, e) for s, e, _ in line]
            for s, e, _, j in pieces(gaps, spans_of_line):
                idle[line[j][2]] += (e - s) / 1e9
                covered.append((s, e))
        idle[program_names["none"]] += (sum(e - s for s, e in gaps) - sum(
            e - s for s, e in merge(covered))) / 1e9
        kernel_host += sum(e - s for s, e, _, _ in
                           pieces(gaps, launches)) / 1e9
        for k, ivs in kernel_iv.items():
            ops = merge([(s, e) for s, e, n in clipped
                         if kernel_of(n, trace_names["kernels"]) == k])
            kernel_dev[k] += sum(e - s for s, e, _, _ in
                                 pieces(ops, merge(ivs))) / 1e9
    n_dev = max(1, len(device))
    return {"window_s": (w1 - w0) / 1e9,
            "devices": len(device),
            "spans": stats,
            "idle": {n: v / n_dev for n, v in
                     sorted(idle.items(), key=lambda kv: -kv[1])},
            "kernel_host_s": kernel_host / n_dev,
            "kernel_device_s": {k: v / n_dev for k, v in kernel_dev.items()}}


def load_events(path: Path, trace_names: dict, chips: int, keep) -> tuple:
    """The first ``chips`` device planes' operations, as
    ``trace.read_trace`` takes them, and every host thread line's events
    whose name ``keep`` accepts."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = sorted((p for p in pd.planes
                     if p.name.startswith(trace_names["device_plane"])),
                    key=lambda p: p.name)[:chips]
    device = {plane.name: [(ev.name, int(ev.start_ns), int(ev.end_ns))
                           for line in plane.lines
                           if line.name in trace_names["op_lines"]
                           for ev in line.events]
              for plane in planes}
    host: dict[str, list[tuple[str, int, int]]] = {}
    for plane in pd.planes:
        if plane.name.startswith(trace_names["host_plane"]):
            for line in plane.lines:
                host[f"{plane.name}/{line.name}"] = [
                    (ev.name, int(ev.start_ns), int(ev.end_ns))
                    for ev in line.events if keep(ev.name)]
    return device, host


def read_program(path: Path, trace_names: dict, program_names: dict,
                 chips: int) -> dict:
    """Reduce one recorded trace: every marked span, every host line."""
    mark, window = program_names["mark"], trace_names["window"]
    device, host = load_events(
        path, trace_names, chips,
        lambda n: n.startswith(mark) or n == window)
    return reduce_program(device, host, trace_names, program_names)
