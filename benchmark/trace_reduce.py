"""From a profiler trace to numbers: the one reduction every PR shares.

`load_xplane` turns JAX's ``.xplane.pb`` into neutral events
``[plane, line, name, start_ns, dur_ns]`` (device lines whole, host lines
only the benchmark's own ``bench:`` annotations); everything below works
on such a list, so it is checked against the small recorded trace in
``testdata/`` with no profiler and no chip (`selfcheck.py`).

Lines of a TPU device plane as the profiler writes them (read from a
v5e trace, PR 23): ``XLA Ops`` (one event an executed HLO op, named by its
whole HLO instruction), ``Async XLA Ops`` (copy-start/slice-start ... that
overlap them), ``XLA Modules`` (one event a program run, ``jit_<name>(id)``)
and ``Steps``.  Busy time is the union of the ``XLA Ops`` intervals.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION = "bench:"
WINDOW = "bench:window"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|async-collective)")


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


_OP = re.compile(r"%?([\w.\-]+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """The profiler names a device op by its whole HLO instruction;
    keep the instruction's name, and a custom call's target beside it
    (``self_attn.72 [tpu_custom_call]``: a Pallas kernel)."""
    op = _OP.match(text)
    target = _TARGET.search(text)
    name = op.group(1) if op else text[:64]
    return f"{name} [{target.group(1)}]" if target else name


def load_xplane(path: str) -> list:
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if device:
                    events.append([plane.name, line.name,
                                   short_name(ev.name), int(ev.start_ns),
                                   int(ev.duration_ns)])
                elif ev.name.startswith(ANNOTATION):
                    events.append([plane.name, line.name, ev.name,
                                   int(ev.start_ns), int(ev.duration_ns)])
    return events


def describe(events: list, per_line: int = 12) -> list[str]:
    """Planes, lines, event counts and a few names: read one trace by
    hand before trusting code written against it."""
    seen: dict = {}
    for plane, line, name, start, dur in events:
        rec = seen.setdefault((plane, line), {"n": 0, "names": {}})
        rec["n"] += 1
        rec["names"][name] = rec["names"].get(name, 0) + dur
    out = []
    for (plane, line), rec in sorted(seen.items()):
        top = sorted(rec["names"].items(), key=lambda kv: -kv[1])
        out.append(f"{plane} | {line} | {rec['n']} events | " + "; ".join(
            f"{n} {d / 1e6:.3f}ms" for n, d in top[:per_line]))
    return out


def thin(events: list, gap_ns: int = 1000) -> list:
    """A smaller trace with the same busy intervals: drop the lines the
    reductions do not read, and merge runs of ``XLA Ops`` of one kind
    (`base_name`) that follow each other within `gap_ns` into one event
    named by the kind.  How ``testdata/recorded.events.json`` was cut
    down; idle gaps shorter than `gap_ns` inside such a run are lost."""
    keep = [e for e in events if e[1] in (MODULES_LINE,)
            or e[2].startswith(ANNOTATION)]
    ops = sorted((e for e in events if e[1] == OPS_LINE),
                 key=lambda e: (e[0], e[3]))
    cur = None
    for e in ops:
        kind = base_name(e[2])
        if (cur is not None and cur[0] == e[0] and cur[2] == kind
                and e[3] - (cur[3] + cur[4]) <= gap_ns):
            cur[4] = max(cur[3] + cur[4], e[3] + e[4]) - cur[3]
        else:
            cur = [e[0], e[1], kind, e[3], e[4]]
            keep.append(cur)
    return keep


# ------------------------------------------------------------- primitives

def device_planes(events: list) -> list[str]:
    """Device planes that ran ops, in name order (one a chip)."""
    return sorted({e[0] for e in events
                   if e[0].startswith("/device:") and e[1] == OPS_LINE})


def window_of(events: list) -> tuple[int, int]:
    """The traced window: the benchmark's own ``bench:window``
    annotation, on the profiler's clock."""
    spans = [(e[3], e[3] + e[4]) for e in events if e[2] == WINDOW]
    if not spans:
        raise ValueError("the trace has no bench:window annotation")
    return min(s for s, _ in spans), max(t for _, t in spans)


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(t, hi)) for s, t in intervals
            if min(t, hi) > max(s, lo)]


def union(intervals) -> list[tuple[int, int]]:
    out: list = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def total(intervals) -> int:
    return sum(t - s for s, t in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of the (merged) intervals `a` not covered by merged `b`."""
    out, j = [], 0
    for s, t in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < t:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < t:
            out.append((cur, t))
    return out


def op_intervals(events, plane, lo, hi, match=None):
    return _clip([(e[3], e[3] + e[4]) for e in events
                  if e[0] == plane and e[1] == OPS_LINE
                  and (match is None or match(e[2]))], lo, hi)


def base_name(name: str) -> str:
    """``fusion.123`` and ``fusion.18.remat2`` -> ``fusion``: one row a
    kind of op (a custom call keeps its target)."""
    op, _, target = name.partition(" ")
    op = re.sub(r"(\.remat\d*|\.\d+)+$", "", op) or op
    return f"{op} {target}".strip()


# ------------------------------------------------------------- reductions

def busy(events: list) -> dict:
    """Seconds an op ran on the device inside the window, averaged over
    the chips that ran ops, and the window's length."""
    lo, hi = window_of(events)
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace holds no device operations")
    per_chip = [total(union(op_intervals(events, p, lo, hi)))
                for p in planes]
    return {"busy_s": sum(per_chip) / len(per_chip) / 1e9,
            "window_s": (hi - lo) / 1e9, "chips": len(planes),
            "busy_s_per_chip": [b / 1e9 for b in per_chip]}


def top_ops(events: list, n: int = 10) -> list:
    """[name, seconds] of the ops that took most device time on the
    first chip, inside the window."""
    lo, hi = window_of(events)
    plane = device_planes(events)[0]
    sums: dict = {}
    for e in events:
        if e[0] == plane and e[1] == OPS_LINE:
            s, t = max(e[3], lo), min(e[3] + e[4], hi)
            if t > s:
                k = base_name(e[2])
                sums[k] = sums.get(k, 0) + (t - s)
    top = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def annotations(events: list) -> list[tuple[int, int, str]]:
    """(start, end, name) of the benchmark's host annotations other than
    the window, by start."""
    return sorted((e[3], e[3] + e[4], e[2][len(ANNOTATION):])
                  for e in events
                  if e[2].startswith(ANNOTATION) and e[2] != WINDOW)


def idle_gaps(events: list, n: int = 10) -> list:
    """[label, seconds]: the first chip's idle time inside the window,
    summed by what the host had last begun when the gap opened:
    ``in:<annotation>`` where that span was still open (the host was
    inside the dispatch or the hook), ``since:<annotation>`` where it had
    ended (the host was in the program's own code after it), and
    ``outside-any-span`` before the first."""
    lo, hi = window_of(events)
    plane = device_planes(events)[0]
    gaps = subtract([(lo, hi)], union(op_intervals(events, plane, lo, hi)))
    notes = annotations(events)
    starts = [a[0] for a in notes]
    import bisect

    sums: dict = {}
    for s, t in gaps:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0:
            label = "outside-any-span"
        else:
            label = ("in:" if s < notes[i][1] else "since:") + notes[i][2]
        sums[label] = sums.get(label, 0) + (t - s)
    top = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def program_runs(events: list) -> list[tuple[int, int, str]]:
    """(start, end, label) of each program run on the first chip inside
    the window, labelled by the latest host annotation begun before it
    (the dispatch that launched it)."""
    lo, hi = window_of(events)
    planes = sorted({e[0] for e in events if e[0].startswith("/device:")
                     and e[1] == MODULES_LINE})
    if not planes:
        return []
    notes = annotations(events)
    starts = [a[0] for a in notes]
    import bisect

    runs = []
    for e in sorted((e for e in events
                     if e[0] == planes[0] and e[1] == MODULES_LINE),
                    key=lambda e: e[3]):
        if e[3] < lo or e[3] + e[4] > hi:
            continue
        i = bisect.bisect_right(starts, e[3]) - 1
        runs.append((e[3], e[3] + e[4], notes[i][2] if i >= 0 else ""))
    return runs


def program_seconds(events: list, label: str) -> list[float]:
    return [(t - s) / 1e9 for s, t, l in program_runs(events) if l == label]


def between_programs(events: list) -> list[float]:
    """Seconds from the end of one program run to the start of the next,
    first chip."""
    runs = program_runs(events)
    return [(b[0] - a[1]) / 1e9 for a, b in zip(runs, runs[1:])
            if b[0] >= a[1]]


def op_seconds(events: list, pattern: str) -> dict:
    """Device seconds of the ops whose name matches `pattern`, first
    chip, inside the window, with how many ran."""
    lo, hi = window_of(events)
    plane = device_planes(events)[0]
    rx = re.compile(pattern)
    got = op_intervals(events, plane, lo, hi, lambda n: bool(rx.search(n)))
    return {"seconds": total(got) / 1e9, "count": len(got)}


def collective_exposed(events: list) -> dict:
    """Collective-op time, and the part of it during which no other op
    ran on that chip, averaged over chips."""
    lo, hi = window_of(events)
    coll = expo = 0
    planes = device_planes(events)
    for p in planes:
        c = union(op_intervals(events, p, lo, hi,
                               lambda n: bool(COLLECTIVE.match(n))))
        other = union(op_intervals(events, p, lo, hi,
                                   lambda n: not COLLECTIVE.match(n)))
        coll += total(c)
        expo += total(subtract(c, other))
    n = max(len(planes), 1)
    return {"collective_s": coll / n / 1e9, "exposed_s": expo / n / 1e9,
            "window_s": (hi - lo) / 1e9}
