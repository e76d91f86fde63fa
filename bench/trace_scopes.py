"""Reads the serving engine's name scopes and host stages from a profiler
trace, on top of ``bench/trace_reduce.py``.

``load`` returns what ``trace_reduce.load`` returns, with the engine's
beat stages (``engine.*`` host spans) among the host spans, their metadata
in ``host_meta`` (one dict per host span, in the same order), and the
name-scope path of every op that ran in ``op_paths`` (program event name ->
op -> path).  The op events name only their HLO instruction, so the paths
come from the compiled programs that the profiler stores in its
``/host:metadata`` plane.

``reduce`` returns what ``trace_reduce.reduce`` returns for the same trace
(``busy_s``, ``window_s`` and ``modules`` unchanged), except that:

* ``device_ops`` ranks ops by *self* time: an op event's interval within
  the window less the op events nested in it, since a loop's event holds
  its body's ops and an inclusive sum counts each of them twice;
* ``idle_gaps`` names each gap by the innermost host span that covers
  most of it (an ``engine.*`` stage inside ``bench.step``), by the span
  that covers the most of it where none covers half, ``host.other``
  where none does;

and adds:

* ``scopes``: of the program executions counted in ``modules``, the self
  seconds of their ops by the innermost of ``SCOPES`` on each op's path
  (``unscoped`` where there is none), per program;
* ``waves``: for each admission wave (``_prefill_chunks``) that began in
  the window, the seconds from the end of the ``engine.wave`` span that
  dispatched it to its start on the device, and the arrival ordinals of
  the requests it carried.

The per-layer metrics ``predecode_ms``, ``decode_linear_ms``,
``decode_attention_ms``, ``lm_head_ms`` and ``wave_queue_ms``
(``bench/metrics/``) read ``scopes`` and ``waves``.
"""

from __future__ import annotations

import bisect

from bench import trace_reduce
from bench.trace_reduce import WINDOW_SPAN, module_name

WAVE_SPAN, WAVE_PROGRAM = "engine.wave", "_prefill_chunks"
DECODE_BLOCK = "_decode_block_dev"
HOST_SPANS = ("bench.", "engine.")
# the name scopes of the served programs (``jax.named_scope`` in the model
# and the engine); an op belongs to the innermost one on its path.  An
# architecture whose programs carry scopes of their own adds them
# (``bench.archs.scopes``), and passes the whole tuple as ``scopes``.
SCOPES = ("predecode", "embed", "qkv", "attention", "o_proj", "gate_up",
          "down", "lm_head", "sample")
UNSCOPED = "unscoped"


def load(path: str, scopes: tuple = SCOPES) -> dict:
    from jax.profiler import ProfileData

    trace = trace_reduce.load(path)
    host, meta = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPANS):
                        host.append((e.name, e.start_ns, e.duration_ns))
                        meta.append({k: str(v) for k, v in e.stats})
    with open(path, "rb") as f:
        paths = hlo_op_paths(f.read(), scopes)
    return {"devices": trace["devices"], "host": host, "host_meta": meta,
            "op_paths": _paths_of_ops_run(trace["devices"], paths)}


def scope_of(path: str, scopes: tuple = SCOPES) -> str:
    """The innermost of ``scopes`` on a name-scope path."""
    for part in reversed(path.split("/")):
        if part in scopes:
            return part
    return UNSCOPED


# -- the compiled programs in the profiler's metadata plane ------------------
#
# Field numbers of the protocol buffers read here: XSpace.planes 1;
# XPlane.name 2, .event_metadata 4 (map entry: key 1, value 2);
# XEventMetadata.name 2, .stats 5; XStat.bytes_value 6; HloProto.hlo_module
# 1; HloModuleProto.computations 3; HloComputationProto.instructions 2,
# .id 5; HloInstructionProto.name 1, .opcode 2, .metadata 7, .id 35,
# .operand_ids 36, .called_computation_ids 38; OpMetadata.op_name 2.

def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of each field of one protocol buffer
    message: an int for a varint, a memoryview for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _ints(value) -> list:
    """A repeated integer field, packed (bytes) or not (one int)."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _instruction_paths(module, scopes: tuple) -> dict:
    """Instruction name -> name-scope path in one ``HloModuleProto``.

    A fusion carries the path of its fused root, which may lie outside
    the scope of the matrix product it holds (a residual add fused onto a
    GEMM), so a fusion takes the path of the first scoped dot,
    convolution or custom call it calls, else its own if scoped, else
    that of the first scoped instruction it calls.  An instruction the
    compiler added carries no path at all (the copy that lays out an
    expanded weight for the loop that reads it): it takes the path of
    its first operand, followed through such instructions."""
    comps, insts = {}, {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        cid, members = None, []
        for g, v in _fields(comp):
            if g == 5:
                cid = v
            elif g == 2:
                name = opcode = path = ""
                iid, operands, called = None, [], []
                for h, w in _fields(v):
                    if h == 1:
                        name = _text(w)
                    elif h == 2:
                        opcode = _text(w)
                    elif h == 7:
                        path = next((_text(x) for k, x in _fields(w)
                                     if k == 2), "")
                    elif h == 35:
                        iid = w
                    elif h == 36:
                        operands += _ints(w)
                    elif h == 38:
                        called += _ints(w)
                members.append((name, opcode, path, called))
                insts[iid] = (name, opcode, path, called, operands)
        comps[cid] = members
    heavy = ("dot", "convolution", "custom-call")
    paths = {}
    for iid, (name, opcode, path, called, _) in insts.items():
        if opcode == "fusion":
            inner = [m for c in called for m in comps.get(c, ())
                     if scope_of(m[2], scopes) != UNSCOPED]
            pick = ([m[2] for m in inner if m[1] in heavy]
                    + ([path] if scope_of(path, scopes) != UNSCOPED
                       else [])
                    + [m[2] for m in inner])
            path = pick[0] if pick else path
        paths[iid] = path
    out = {}
    for iid, (name, _, _, _, operands) in insts.items():
        path, seen = paths[iid], {iid}
        while not path and operands and operands[0] not in seen:
            seen.add(operands[0])
            path = paths.get(operands[0], "")
            operands = insts.get(operands[0], ("",) * 5)[4]
        if path:
            out[name] = path
    return out


def hlo_op_paths(xspace: bytes, scopes: tuple = SCOPES) -> dict:
    """Program event name (``jit_f(123)``) -> instruction -> name-scope
    path, for every compiled program that the profiler stored in the
    ``/host:metadata`` plane of a serialized ``XSpace``."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if next((_text(v) for g, v in fields if g == 2), "") != \
                "/host:metadata":
            continue
        for g, entry in fields:
            if g != 4:
                continue
            meta = next((v for h, v in _fields(entry) if h == 2), b"")
            name, protos = "", []
            for h, v in _fields(meta):
                if h == 2:
                    name = _text(v)
                elif h == 5:
                    protos += [w for k, w in _fields(v) if k == 6]
            for proto in protos:
                for k, module in _fields(proto):
                    if k == 1:
                        out[name] = _instruction_paths(module, scopes)
    return out


def _executions(dev: dict) -> tuple:
    """The device's program executions sorted by start, and their
    starts."""
    mods = sorted(dev["modules"], key=lambda m: m[1])
    return mods, [m[1] for m in mods]


def _module_of(mods, starts, t):
    """The program execution running at time ``t``, if any."""
    k = bisect.bisect_right(starts, t) - 1
    if k >= 0 and t < mods[k][1] + mods[k][2]:
        return mods[k]
    return None


def _paths_of_ops_run(devices, paths) -> dict:
    """The paths of the ops that ran, by the event name of their program
    (or of a program compiled from the same function)."""
    by_fn = {module_name(k): v for k, v in paths.items()}
    out: dict = {}
    for dev in devices:
        mods, starts = _executions(dev)
        for name, start, _ in dev["ops"]:
            m = _module_of(mods, starts, start)
            if m is None:
                continue
            table = paths.get(m[0]) or by_fn.get(module_name(m[0]), {})
            if name in table:
                out.setdefault(m[0], {})[name] = table[name]
    return out


# -- the reduction -----------------------------------------------------------

def _overlap(s, e, lo, hi):
    return max(0, min(e, hi) - max(s, lo))


def self_times(ops, lo, hi) -> list:
    """Each op event as ``[name, start, duration, self, self in the
    window]``: its interval, whole and within ``[lo, hi)``, less the op
    events nested directly in it."""
    events = sorted(ops, key=lambda o: (o[1], -o[2]))
    own = [[n, s, d, d, _overlap(s, s + d, lo, hi)] for n, s, d in events]
    stack = []
    for i, (_, s, d) in enumerate(events):
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = own[stack[-1]]
            e = min(s + d, parent[1] + parent[2])
            parent[3] -= e - s
            parent[4] -= _overlap(s, e, lo, hi)
        stack.append(i)
    return own


def name_gap(host, s, e) -> str:
    """The innermost host span that covers most of ``[s, e)``."""
    best, best_key = "host.other", None
    for name, hs, hd in host:
        if name == WINDOW_SPAN:
            continue
        ov = _overlap(hs, hs + hd, s, e)
        if ov <= 0:
            continue
        most = 2 * ov > e - s
        key = (most, -hd if most else ov)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def _gaps(trace, lo, hi, top) -> list:
    gaps = []
    for dev in trace["devices"]:
        busy = trace_reduce._union(
            (s, e) for _, s, e in
            trace_reduce._clip(dev["ops"] or dev["modules"], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[name_gap(trace["host"], s, e), (e - s) * 1e-9]
            for s, e in gaps[:top]]


def _waves(trace, lo, hi) -> list:
    """Pair each admission wave's execution on the first device with the
    ``engine.wave`` span that dispatched it.  The device runs programs in
    the order they were dispatched, so the k-th wave span that carries
    requests (a wave that dispatched nothing carries none) goes with the
    k-th wave program.  An execution that began before the next span did
    was dispatched before the trace began, and is passed over."""
    if not trace["devices"]:
        return []
    spans = sorted((s, s + d, m["requests"]) for (n, s, d), m
                   in zip(trace["host"], trace.get("host_meta", ()))
                   if n == WAVE_SPAN and "requests" in m)
    execs = sorted(m[1] for m in trace["devices"][0]["modules"]
                   if module_name(m[0]) == WAVE_PROGRAM)
    out, j = [], 0
    for start in execs:
        if j < len(spans) and spans[j][0] <= start:
            _, end, reqs = spans[j]
            j += 1
            if lo <= start < hi:
                out.append({"queue_s": (start - end) * 1e-9,
                            "requests": [int(r) for r in reqs.split()]})
    return out


def reduce(trace: dict, top: int = 10, scopes: tuple = SCOPES) -> dict:
    out = trace_reduce.reduce(trace, top)
    lo, hi = trace_reduce.window(trace)
    op_paths = trace.get("op_paths", {})
    ops: dict = {}
    by_scope: dict = {}
    for dev in trace["devices"]:
        counted = {(m[0], m[1]) for m in dev["modules"] if lo <= m[1] < hi}
        mods, starts = _executions(dev)
        for name, start, _, own, own_w in self_times(dev["ops"], lo, hi):
            if own_w > 0:
                ops[name] = ops.get(name, 0.0) + own_w * 1e-9
            m = _module_of(mods, starts, start)
            if m is not None and (m[0], m[1]) in counted:
                sc = scope_of(op_paths.get(m[0], {}).get(name, ""), scopes)
                by = by_scope.setdefault(module_name(m[0]), {})
                by[sc] = by.get(sc, 0.0) + own * 1e-9
    out.update({
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": _gaps(trace, lo, hi, top),
        "scopes": by_scope,
        "waves": _waves(trace, lo, hi),
    })
    return out


def top_scopes(scopes: dict, top: int = 10) -> list:
    """``[program/scope, seconds]``, the most device self time first: the
    breakdown's ``device_scopes``."""
    flat = [[f"{prog}/{sc}", sec] for prog, by in scopes.items()
            for sc, sec in by.items()]
    return sorted(flat, key=lambda kv: -kv[1])[:top]


# -- what the per-layer metrics read -----------------------------------------

def scope_seconds(run, program, names):
    """Device self seconds of ``program``'s ops under the scopes
    ``names``, or None where none of them ran (a program without them)."""
    by = run["summary"].get("scopes", {}).get(program, {})
    if not any(n in by for n in names):
        return None
    return sum(by.get(n, 0.0) for n in names)


def decode_block_ms(run, names, per_tick: bool = True):
    """Milliseconds under the scopes ``names`` in the fused decode block,
    per execution, or per tick (executions x ticks per block)."""
    m = run["summary"]["modules"].get(DECODE_BLOCK)
    sec = scope_seconds(run, DECODE_BLOCK, names)
    if not m or not m["count"] or sec is None:
        return None
    ticks = run["engine"]["decode_block"] if per_tick else 1
    return 1e3 * sec / (m["count"] * ticks)
