"""From a jax profiler trace (``.xplane.pb``) to device numbers.

- busy and idle: the union of the intervals in which an operation ran
  on the device, clipped to the traced window, averaged over the chips
  used; idle share is 1 less busy over the window.
- device time per named program: the events of the ``XLA Modules``
  line, one per launch, grouped by the program's name: each launch that
  lies whole inside the window (``launches``), and the seconds of every
  launch that fall inside it, cut ones too (``program_s``).
- the longest idle gaps, each labelled with the innermost host span
  (the program's tracer, same monotonic clock through a sync marker
  the harness writes into the trace) open at the gap's middle.
- the ``breakdown`` of a traced run: the ten device operations that
  took most time (leaves only: an operation that contains others, as a
  ``while`` does its loop, is not counted beside them) and the ten
  labels that idle most.

All times are seconds. Trace timestamps are nanoseconds from the
profile's start; ``sync`` = (trace seconds, monotonic seconds) of the
same instant maps one clock to the other.
"""

from __future__ import annotations

import re

SYNC_NAME = "bench.sync"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
#: a gap shorter than this is not looked up among the host's spans
SHORT_GAP_S = 50e-6


def union(intervals: list) -> list:
    """Merged, sorted [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list, lo: float, hi: float) -> list:
    """The idle intervals of [lo, hi) given merged ``busy``."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def leaves(rows: list) -> list:
    """The operations that contain no other: a ``while`` spans its
    whole loop, and what says where the time goes is what runs inside
    it. ``rows`` are (name, start, duration)."""
    ordered = sorted(rows, key=lambda r: (r[1], -r[2]))
    out = []
    for i, (name, s, d) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and s <= nxt[1] < s + d \
                and not (nxt[1] == s and nxt[2] == d):
            continue
        out.append((name, s, d))
    return out


def op_label(event_name: str) -> str:
    """``%fusion.87 = f32[16384]{...} fusion(...)`` -> the op's name
    and the start of what it computes."""
    head, _, rest = event_name.partition(" = ")
    return (head + " " + rest[:48]).strip()


def program_name(event_name: str) -> str:
    """``jit_joint(1234567)`` -> ``jit_joint``."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def label_gap(mid: float, spans: list) -> str:
    """The innermost span (latest start) open at monotonic ``mid``;
    ``spans`` are (name, start_s, dur_s)."""
    best = None
    for name, start, dur in spans:
        if start <= mid < start + dur and (best is None or start > best[1]):
            best = (name, start)
    return best[0] if best else "no span open"


def read_planes(path: str, rehearsal: bool = False) -> dict:
    """{"devices": {plane: {"modules": [...], "ops": [...]}},
    "sync_trace_s": float | None}; events are (name, start_s, dur_s)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return planes_of(data, rehearsal)


def planes_of(data, rehearsal: bool = False) -> dict:
    devices: dict = {}
    sync = None
    lines_read = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            host_xla = (rehearsal and plane.name == "/host:CPU"
                        and line.name.startswith("tf_XLA"))
            if is_device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            n = 0
            for ev in line.events:
                n += 1
                if ev.name == SYNC_NAME and sync is None:
                    sync = ev.start_ns * 1e-9
                if not (is_device or host_xla) or ev.duration_ns <= 0:
                    continue
                row = (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                if is_device:
                    dev = devices.setdefault(
                        plane.name, {"modules": [], "ops": []})
                    if line.name == MODULES_LINE:
                        dev["modules"].append(row)
                    elif line.name == OPS_LINE:
                        dev["ops"].append(row)
                else:
                    # the rehearsal's stand-in for a device: the CPU
                    # client's executor threads
                    dev = devices.setdefault(
                        "/host:CPU", {"modules": [], "ops": []})
                    dev["ops"].append(row)
            lines_read.append([plane.name, line.name, n])
    return {"devices": devices, "sync_trace_s": sync,
            "lines_read": lines_read}


def reduce_trace(planes: dict, lo: float, hi: float, spans: list,
                 to_monotonic: float) -> dict:
    """Device numbers of the window [lo, hi) (trace seconds).

    ``spans`` are host spans on the monotonic clock and ``to_monotonic``
    is what to add to a trace second to get a monotonic one."""
    window = hi - lo
    busy_total = 0.0
    op_time: dict = {}
    launches: dict = {}
    program_s: dict = {}
    gap_time: dict = {}
    longest: list = []
    for dev in planes["devices"].values():
        rows = dev["ops"] or dev["modules"]
        busy = clip(union([[s, s + d] for _n, s, d in rows]), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for name, s, d in leaves(rows):
            inside = min(s + d, hi) - max(s, lo)
            if inside > 0:
                label = op_label(name)
                op_time[label] = op_time.get(label, 0.0) + inside
        for name, s, d in dev["modules"]:
            if s >= lo and s + d <= hi:
                launches.setdefault(program_name(name), []).append(d)
            inside = min(s + d, hi) - max(s, lo)
            if inside > 0:
                prog = program_name(name)
                program_s[prog] = program_s.get(prog, 0.0) + inside
        for s, e in gaps(busy, lo, hi):
            if e - s < SHORT_GAP_S:
                # between two operations of one program: not the host's
                label = f"gaps under {SHORT_GAP_S * 1e6:.0f} us"
            else:
                label = label_gap((s + e) / 2 + to_monotonic, spans)
                longest.append((e - s, label))
            gap_time[label] = gap_time.get(label, 0.0) + (e - s)
    n = max(len(planes["devices"]), 1)
    top = lambda d: [[k, v / n] for k, v in sorted(     # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    longest.sort(reverse=True)
    return {
        "busy_s": busy_total / n,
        "window_s": window,
        "idle_share": 1.0 - busy_total / n / window if window > 0 else None,
        "launches": launches,
        "program_s": {k: v / n for k, v in program_s.items()},
        "longest_gaps": [[label, s] for s, label in longest[:10]],
        "breakdown": {"device_ops": top(op_time),
                      "idle_gaps": top(gap_time)},
    }
