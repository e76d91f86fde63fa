"""Reduces a JAX profiler trace to the numbers the per-layer metrics read.

``load`` keeps, from the ``.xplane.pb`` the profiler wrote, what the
reduction needs: on each device plane the ``XLA Modules`` line (one event
per execution of a compiled program) and the ``XLA Ops`` line (one event per
operation), and on the host the benchmark's own spans, named ``bench.*``.
Times are nanoseconds on the profiler's clock, which it shares between host
and device.  ``reduce`` then works on that plain structure, so a small
recorded trace can be kept as JSON and checked without a chip.

Within the window of the host span ``bench.traced``:

* busy: the union of the device's op intervals (module intervals where a
  plane has no op line), averaged over the device planes;
* modules: device seconds and executions of each program that began in
  the window, by the jitted function's name (``jit__decode_block_dev(42)``
  -> ``_decode_block_dev``);
* ops: device seconds of each operation name;
* idle gaps: the intervals in which no device op ran, each named by the
  ``bench.*`` span that covered most of it (``host.other`` if none did).
"""

from __future__ import annotations

import re

WINDOW_SPAN = "bench.traced"
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULE_LINE, OPS_LINE):
                    lines[line.name] = [(op_name(e.name), e.start_ns,
                                         e.duration_ns)
                                        for e in line.events]
            if lines:
                devices.append({"name": plane.name,
                                "modules": lines.get(MODULE_LINE, []),
                                "ops": lines.get(OPS_LINE, [])})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def op_name(event_name: str) -> str:
    """An op event is named by its whole HLO instruction; keep the name
    (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def _clip(events, lo, hi):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window(trace: dict) -> tuple:
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _name_gap(host, s, e) -> str:
    best, best_ov = "host.other", 0.0
    for name, hs, hd in host:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, hs + hd) - max(s, hs)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce(trace: dict, top: int = 10) -> dict:
    lo, hi = window(trace)
    span_s = (hi - lo) * 1e-9
    modules: dict = {}
    ops: dict = {}
    busy_s = 0.0
    gaps = []
    for dev in trace["devices"]:
        for name, start, dur in dev["modules"]:
            if lo <= start < hi:  # whole executions that began inside
                m = modules.setdefault(module_name(name),
                                       {"seconds": 0.0, "count": 0})
                m["seconds"] += dur * 1e-9
                m["count"] += 1
        for name, s, e in _clip(dev["ops"], lo, hi):
            ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9
        busy = _union((s, e) for _, s, e in
                      _clip(dev["ops"] or dev["modules"], lo, hi))
        busy_s += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n_dev = max(1, len(trace["devices"]))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "devices": len(trace["devices"]),
        "window_s": span_s,
        "busy_s": busy_s / n_dev,
        "modules": modules,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_name_gap(trace["host"], s, e), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }
