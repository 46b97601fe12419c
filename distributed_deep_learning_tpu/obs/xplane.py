"""Read a profiler trace (``.xplane.pb``) the way an operator asks of it:
which named scope of which program the device spent its time in, and
which host phase the device was waiting on while it idled.

Both questions need names the program itself wrote into the trace: the
``ddl:`` host spans of :func:`..obs.trace.span` (same xplane, same clock
as the device ops) and the ``jax.named_scope`` / Flax module path of
every HLO instruction (its ``op_name``).  ``scripts/obs_report.py
--xplane`` renders what this module reduces.

Layout of a trace as JAX 0.9 writes it.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed
HLO op and ``XLA Modules`` one per program run; host threads are lines of
``/host:CPU``, and a ``TraceAnnotation`` is an event on its thread's
line.  The CPU backend has no device plane: its ops are events with an
``hlo_op`` stat on the host plane's XLA worker lines, which is enough for
the tests to walk every path here without a chip.

A device op's event carries no ``op_name`` (read on a v5e, PR 24: its
name is the whole HLO instruction text, its stats are timings).  The
scope comes from the compiled programs themselves: the profiler stores
each module's ``HloProto`` as a stat on the ``/host:metadata`` plane, and
`hlo_scopes` reads instruction name -> ``op_name`` out of it with a few
lines of protobuf wire format (``jax.profiler.ProfileData`` does not
expose that plane's bytes, and nothing else here needs a proto library).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Optional

from .trace import SPAN_PREFIX

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_PREFIX = "bench:"
WINDOW = "bench:window"
OUTSIDE = "outside any span"
_HLO_NAME = re.compile(r"%?([\w.\-]+)")


def newest(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under what ``--profile-dir`` (or
    ``jax.profiler.start_trace``) wrote."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files and trace_dir.endswith(".xplane.pb"):
        files = [trace_dir]
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        c = buf[i]
        i += 1
        value |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return value, i


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: varints
    as ints, length-delimited fields as memoryviews."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, wire, value


def _sub(buf, field):
    return [v for f, w, v in _fields(buf) if f == field and w == 2]


def _text(buf, field) -> str:
    got = _sub(buf, field)
    return bytes(got[0]).decode("utf-8", "replace") if got else ""


def hlo_scopes(path: str) -> dict:
    """``{module name: {instruction name: op_name}}`` from the
    ``HloProto`` of every program the trace holds (XSpace.planes >
    ``/host:metadata`` > event_metadata > stats["Hlo Proto"] >
    hlo_module > computations > instructions > metadata.op_name).  An
    instruction the compiler made carries no metadata of its own: a
    fusion takes the ``op_name`` most of the instructions it fused carry,
    anything else (a layout copy) its first named operand's."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for plane in _sub(space, 1):
        if _text(plane, 2) != "/host:metadata":
            continue
        for entry in _sub(plane, 4):            # map<id, XEventMetadata>
            for meta in _sub(entry, 2):
                for stat in _sub(meta, 5):
                    for proto in _sub(stat, 6):          # bytes_value
                        for module in _sub(proto, 1):    # HloProto
                            out.setdefault(_text(module, 1), {}).update(
                                _module_scopes(module))
    return out


def _ints(msg, field) -> list:
    """A repeated int64 field's values, packed or not."""
    out = []
    for f, wire, value in _fields(msg):
        if f != field:
            continue
        if wire == 0:
            out.append(value)
        else:
            i = 0
            while i < len(value):
                v, i = _varint(value, i)
                out.append(v)
    return out


def _module_scopes(module) -> dict:
    """HloInstructionProto: name 1, metadata 7 (op_name 2), id 35,
    operand_ids 36, called_computation_ids 38; HloComputationProto:
    instructions 2, id 5."""
    names: dict = {}        # instruction name -> op_name
    by_id: dict = {}        # instruction id -> name
    inside: dict = {}       # computation id -> {op_name: instructions}
    bare: dict = {}         # name with no op_name -> (computations, operands)
    for comp in _sub(module, 3):
        tally: dict = {}
        for ins in _sub(comp, 2):
            name = _text(ins, 1)
            by_id.update((i, name) for i in _ints(ins, 35))
            md = _sub(ins, 7)
            op = _text(md[0], 2) if md else ""
            if op:
                names[name] = op
                tally[op] = tally.get(op, 0) + 1
            else:
                bare[name] = (_ints(ins, 38), _ints(ins, 36))
        for comp_id in _ints(comp, 5):
            inside[comp_id] = tally
    for _ in range(3):      # a fusion from what it fused, else (a copy the
        for name, (called, operands) in bare.items():   # compiler put in)
            if name in names:                           # from its operand
                continue
            tally = {}
            for c in called:
                for op, n in inside.get(c, {}).items():
                    tally[op] = tally.get(op, 0) + n
            if tally:
                names[name] = max(tally, key=tally.get)
                continue
            for i in operands:
                if names.get(by_id.get(i)):
                    names[name] = names[by_id[i]]
                    break
    return names


def hlo_name(event_name: str) -> str:
    """``%fusion.129 = bf16[...] fusion(...)`` -> ``fusion.129``."""
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def load(path: str) -> dict:
    """``{"ops", "modules", "spans", "scopes", "bytes"}``.

    ``ops``: ``{plane: [(start_ns, end_ns, instruction name, module)]}``
    of the device (on the CPU backend: the one pseudo-device ``cpu``;
    `module` is empty where only the enclosing program run says it);
    ``modules``: ``{plane: [(start_ns, end_ns, name)]}``; ``spans``:
    ``{host line: [(start_ns, end_ns, name, stats)]}`` of the ``ddl:`` and
    ``bench:`` annotations; ``scopes``: `hlo_scopes`.
    """
    from jax.profiler import ProfileData

    ops: dict = {}
    modules: dict = {}
    spans: dict = {}
    planes = list(ProfileData.from_file(path).planes)
    on_cpu = not any(p.name.startswith("/device:") for p in planes)
    for plane in planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                t0 = int(ev.start_ns)
                t1 = t0 + int(ev.duration_ns)
                name = ev.name
                if device:
                    if line.name == OPS_LINE:
                        ops.setdefault(plane.name, []).append(
                            (t0, t1, hlo_name(name), ""))
                    elif line.name == MODULES_LINE:
                        modules.setdefault(plane.name, []).append(
                            (t0, t1, name))
                elif name.startswith((SPAN_PREFIX, BENCH_PREFIX)):
                    spans.setdefault(line.name, []).append(
                        (t0, t1, name, dict(ev.stats)))
                elif on_cpu and not name.startswith(("end: ", "$")):
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        ops.setdefault("cpu", []).append(
                            (t0, t1, name, str(stats.get("hlo_module", ""))))
    for rows in (*ops.values(), *modules.values(), *spans.values()):
        rows.sort()
    return {"ops": ops, "modules": modules, "spans": spans,
            "scopes": hlo_scopes(path), "bytes": os.path.getsize(path)}


# ------------------------------------------------------------- intervals

def _union(intervals) -> list:
    out: list = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def _gaps(lo: int, hi: int, busy: list) -> list:
    out, cur = [], lo
    for s, t in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, t)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, t) for s, t in out if t > s]


def window(trace: dict, plane: Optional[str] = None) -> tuple[int, int]:
    """The benchmark's ``bench:window`` where the trace has one, else
    from the first ``ddl:`` span or device op to the last."""
    for rows in trace["spans"].values():
        for s, t, name, _ in rows:
            if name == WINDOW:
                return s, t
    marks = [(s, t) for rows in trace["spans"].values()
             for s, t, name, _ in rows if name.startswith(SPAN_PREFIX)]
    if not marks:
        marks = [(s, t) for s, t, *_ in trace["ops"].get(
            plane or first_device(trace), [])]
    if not marks:
        raise ValueError("the trace holds neither spans nor device ops")
    return min(s for s, _ in marks), max(t for _, t in marks)


def first_device(trace: dict) -> str:
    if not trace["ops"]:
        raise ValueError("the trace holds no device operations")
    return sorted(trace["ops"])[0]


def innermost(spans: list, prefix: str = SPAN_PREFIX) -> list:
    """Flatten one thread's properly nested spans into disjoint segments
    ``(start, end, name)``, each named by the innermost `prefix` span
    open over it."""
    rows = sorted(((s, t, n) for s, t, n, _ in spans
                   if n.startswith(prefix)), key=lambda r: (r[0], -r[1]))
    out: list = []
    stack: list = []                    # open (end, name), innermost last
    cur = 0
    for s, t, n in rows:
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
                cur = end
        if stack and s > cur:
            out.append((cur, s, stack[-1][1]))
        cur = s
        stack.append((t, n))
    while stack:
        end, name = stack.pop()
        if end > cur:
            out.append((cur, end, name))
            cur = end
    return out


def span_line(trace: dict) -> Optional[str]:
    """The host thread whose ``ddl:`` spans cover most time (the loop's
    own thread)."""
    best, cover = None, 0
    for line, rows in trace["spans"].items():
        c = sum(t - s for s, t, n, _ in rows if n.startswith(SPAN_PREFIX))
        if c > cover:
            best, cover = line, c
    return best


# ------------------------------------------------------------ reductions

def _intersect(a: list, b: list) -> list:
    """Parts of the merged intervals `a` that merged `b` covers."""
    out, j = [], 0
    for s, t in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < t:
            lo, hi = max(s, b[k][0]), min(t, b[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def _charge(gaps: list, segs: list, sums: dict, col: int) -> None:
    """Split each gap over the segments it overlaps; what no segment
    covers goes to OUTSIDE.  ``sums[name][col]`` += ns; ``[2]`` counts
    the pieces of column 0."""
    def add(name, ns):
        rec = sums.setdefault(name, [0, 0, 0])
        rec[col] += ns
        rec[2] += col == 0

    i = 0
    for g0, g1 in gaps:
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        cur, j = g0, i
        while j < len(segs) and segs[j][0] < g1:
            s, t, name = segs[j]
            if s > cur:
                add(OUTSIDE, s - cur)
            lo, hi = max(s, cur), min(t, g1)
            if hi > lo:
                add(name, hi - lo)
            cur = max(cur, hi)
            j += 1
        if cur < g1:
            add(OUTSIDE, g1 - cur)


def idle_by_phase(trace: dict) -> dict:
    """Device idle time inside the window, split over the innermost
    ``ddl:`` span open at each instant of each gap (a gap that begins in
    a wait and ends three phases later is charged to all four, each for
    its own stretch), and told apart by where it lies on the device:
    BETWEEN two program runs (the device has nothing to run: the host's
    time) or INSIDE a run (the program's own bubbles between its ops,
    which no host phase causes).  ``by_phase`` rows are ``[name, seconds
    between programs, seconds inside a program, pieces between]``."""
    plane = first_device(trace)
    lo, hi = window(trace, plane)
    busy = _union((max(s, lo), min(t, hi)) for s, t, *_ in
                  trace["ops"][plane] if min(t, hi) > max(s, lo))
    gaps = _gaps(lo, hi, busy)
    runs = _union((s, t) for s, t, _ in trace["modules"].get(plane, []))
    inside = _intersect(gaps, runs)
    between = _gaps(lo, hi, _union(busy + inside))
    line = span_line(trace)
    segs = innermost(trace["spans"][line]) if line else []
    sums: dict = {}
    _charge(between, segs, sums, 0)
    _charge(inside, segs, sums, 1)
    idle = sum(t - s for s, t in gaps)
    out_ns = sum(sums.get(OUTSIDE, (0, 0))[:2])
    rows = sorted(([n[len(SPAN_PREFIX):] if n != OUTSIDE else n,
                    v[0] / 1e9, v[1] / 1e9, v[2]] for n, v in sums.items()),
                  key=lambda r: -(r[1] + r[2]))
    return {"plane": plane, "thread": line, "window_s": (hi - lo) / 1e9,
            "busy_s": sum(t - s for s, t in busy) / 1e9,
            "idle_s": idle / 1e9,
            "between_s": sum(t - s for s, t in between) / 1e9,
            "inside_s": sum(t - s for s, t in inside) / 1e9,
            "named_s": (idle - out_ns) / 1e9, "by_phase": rows}


_WRAP = re.compile(r"^(?:transpose|jvp|vmap|pmap|remat|checkpoint|"
                   r"custom_jvp|custom_vjp|shard_map)\((.*)\)$")
_INDEX = re.compile(r"_\d+$")
_ARG = re.compile(r"[\w.]*(\[\d+\])?")


def scope_of(op_name: str, depth: int = 3) -> str:
    """``jit(train_step)/transpose(jvp(CausalLM))/layer_7/self_attn/dot``
    -> ``CausalLM/layer_*/self_attn``: the transform wrappers come off,
    the primitive (last segment) comes off, numbered siblings fold, and
    the first `depth` segments are kept.  An op under no scope at all
    keeps its primitive (``(top level) copy``); one named after a program
    argument (a copy of ``args[1]['layer_0']['self_attn']['cached_key']``
    into another layout) reads ``(argument) args[1]``."""
    if not op_name:
        return "(no op_name)"
    if "/" not in op_name:      # a parameter: args[1]['layer_0'][...]
        return "(argument) " + _ARG.match(op_name).group(0)
    segs = []
    for seg in op_name.split("/")[:-1]:
        if seg.startswith(("jit(", "pjit(")):
            continue
        m = _WRAP.match(seg)
        while m:
            seg = m.group(1)
            m = _WRAP.match(seg)
        if seg:
            segs.append(_INDEX.sub("_*", seg))
    return "/".join(segs[:depth]) or f"(top level) {op_name.split('/')[-1]}"


def module_of(name: str) -> str:
    """``jit_paged_decode(1234)`` -> ``jit_paged_decode``."""
    return name.split("(")[0]


def seconds_by_scope(trace: dict, depth: int = 3,
                     pattern: Optional[str] = None) -> dict:
    """Device seconds inside the window by (program, scope), first chip;
    with `pattern` only of the ops whose HLO name matches it (``^copy``
    answers "which scope owns the copies").  An op belongs to the program
    run (``XLA Modules`` event) it starts in, else to its own
    ``hlo_module`` stat.  ``{"op_s", "rows": [[program, scope, seconds,
    events]]}``."""
    import bisect

    plane = first_device(trace)
    lo, hi = window(trace, plane)
    rx = re.compile(pattern) if pattern else None
    runs = trace["modules"].get(plane, [])
    starts = [r[0] for r in runs]
    sums: dict = {}
    scopes = trace["scopes"]
    for s, t, name, mod in trace["ops"][plane]:
        a, b = max(s, lo), min(t, hi)
        if b <= a or (rx is not None and not rx.search(name)):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            mod = module_of(runs[i][2])
        scope = scopes.get(mod, {}).get(name, "")
        rec = sums.setdefault((mod or "?", scope_of(scope, depth)), [0, 0])
        rec[0] += b - a
        rec[1] += 1
    rows = sorted(([p, sc, v / 1e9, n] for (p, sc), (v, n) in sums.items()),
                  key=lambda r: -r[2])
    return {"plane": plane, "rows": rows,
            "op_s": sum(r[2] for r in rows)}


def programs(trace: dict) -> list:
    """``[[module, runs, seconds]]`` on the first chip, inside the
    window."""
    plane = first_device(trace)
    lo, hi = window(trace, plane)
    sums: dict = {}
    for s, t, name in trace["modules"].get(plane, []):
        if s >= lo and t <= hi:
            rec = sums.setdefault(module_of(name), [0, 0])
            rec[0] += 1
            rec[1] += t - s
    if not sums:                # CPU backend: from the ops' own stat
        for s, t, _, mod in trace["ops"][plane]:
            if mod and s >= lo and t <= hi:
                rec = sums.setdefault(mod, [0, 0])
                rec[1] += t - s
    return sorted(([m, n, v / 1e9] for m, (n, v) in sums.items()),
                  key=lambda r: -r[2])


def nesting(trace: dict) -> list:
    """One clock, shown: for each kind of ``ddl:`` span that has a
    ``bench:`` annotation of the same name in the trace, how many lie
    wholly inside one.  ``[[name, inside, total]]``."""
    out = []
    for rows in trace["spans"].values():
        outer: dict = {}
        for s, t, n, _ in rows:
            if n.startswith(BENCH_PREFIX) and n != WINDOW:
                outer.setdefault(n[len(BENCH_PREFIX):], []).append((s, t))
        for kind, wraps in outer.items():
            mine = [(s, t) for s, t, n, _ in rows
                    if n == SPAN_PREFIX + kind]
            if mine:
                inside = sum(any(a <= s and t <= b for a, b in wraps)
                             for s, t in mine)
                out.append([kind, inside, len(mine)])
    return sorted(out)


def span_counts(trace: dict) -> dict:
    """``{name: [count, seconds]}`` of the ``ddl:`` spans, all threads."""
    out: dict = {}
    for rows in trace["spans"].values():
        for s, t, n, _ in rows:
            if n.startswith(SPAN_PREFIX):
                rec = out.setdefault(n[len(SPAN_PREFIX):], [0, 0.0])
                rec[0] += 1
                rec[1] += (t - s) / 1e9
    return out
