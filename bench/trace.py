"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, time
per device operation, host-labelled idle gaps and exposed collective time.

Only JAX is needed: ``jax.profiler.ProfileData`` reads the file.  A device
is a plane named ``/device:TPU:<n>``; its operations are the events of its
``XLA Ops`` line.  The harness's own host spans are the profiler's
``TraceAnnotation`` events whose names start with ``bench.``; the one named
``bench.window`` bounds the measured window, and the others say what the
host was doing in each idle gap of the device.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW = "bench.window"
#: device operations that move data between chips
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"\bsend\b|\brecv\b|send-done|recv-done|psum|ppermute",
    re.IGNORECASE,
)

Interval = tuple[int, int]  # [start_ns, end_ns)


def find_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def union(intervals: Iterable[Interval]) -> list[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Iterable[Interval], holes: list[Interval]) -> list[Interval]:
    """Parts of ``intervals`` not covered by the disjoint sorted ``holes``."""
    out = []
    for a, b in union(intervals):
        cur = a
        for h0, h1 in holes:
            if h1 <= cur:
                continue
            if h0 >= b:
                break
            if h0 > cur:
                out.append((cur, h0))
            cur = max(cur, h1)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def gaps(busy: list[Interval], lo: int, hi: int) -> list[Interval]:
    """The complement of disjoint sorted ``busy`` within [lo, hi)."""
    return subtract([(lo, hi)], busy)


def op_name(event_name: str) -> str:
    """An XLA Ops event is named by its HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``); keep the instruction's name."""
    head = event_name.split(" = ", 1)[0] if " = " in event_name else event_name
    return head.lstrip("%")


def device_ops(pd) -> dict[int, list[tuple[int, int, str]]]:
    """Device id -> its operations as (start_ns, end_ns, name)."""
    out = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                s = int(e.start_ns)
                evs.append((s, s + int(e.duration_ns), op_name(e.name)))
        out[int(m.group(1))] = evs
    return out


def host_spans(pd, prefix: str = HOST_PREFIX) -> list[tuple[int, int, str]]:
    """The harness's own host spans, (start_ns, end_ns, name)."""
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    s = int(e.start_ns)
                    out.append((s, s + int(e.duration_ns), e.name))
    return out


def self_times(ops: list[tuple[int, int, str]]) -> dict[str, float]:
    """Seconds per operation name, each event less the events nested inside
    it (a ``while`` loop's event spans the operations of its body)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, child_ns]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + item[3] / 1e9

    for a, b, nm in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] -= min(b, stack[-1][0]) - a
        stack.append([b, nm, a, b - a])
    while stack:
        close(stack.pop())
    return out


def _label(gap: Interval, spans: list[tuple[int, int, str]]) -> str:
    """The host span that overlaps the gap most, innermost on a tie."""
    best, best_ov, best_len = "none", 0, None
    for s, e, name in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        if ov > best_ov or (ov == best_ov and best_len is not None and e - s < best_len):
            best, best_ov, best_len = name, ov, e - s
    return best


def reduce(pd, *, top: int = 10, steps: int | None = None) -> dict:
    """Reduce a trace to the numbers the benchmark reports.

    Returns ``window_s`` (the ``bench.window`` span), ``busy_s`` (union of
    device operation intervals inside it, averaged over devices),
    ``idle_share``, ``device_ops`` (the ``top`` operations by self time on
    the device, averaged over devices, as [name, seconds]), ``idle_gaps`` (idle time
    inside the window by the host span that overlaps each gap most, as
    [name, seconds], averaged over devices) and ``exposed_collective_s``
    (collective time during which no other operation runs on that device,
    median over devices; per step when ``steps`` is given).
    """
    spans = host_spans(pd)
    wins = [(s, e) for s, e, n in spans if n == WINDOW]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    lo, hi = max(wins, key=lambda w: w[1] - w[0])
    labels = [sp for sp in spans if sp[2] != WINDOW]
    devs = device_ops(pd)
    if not devs:
        raise ValueError("trace holds no device plane with an 'XLA Ops' line")
    n = len(devs)
    busy_s, op_time, idle = 0.0, {}, {}
    exposed = []
    for evs in devs.values():
        ops = [(max(a, lo), min(b, hi), nm) for a, b, nm in evs if min(b, hi) > max(a, lo)]
        busy = union((a, b) for a, b, _ in ops)
        busy_s += total(busy) / 1e9
        for nm, t in self_times(ops).items():
            op_time[nm] = op_time.get(nm, 0.0) + t
        for g in gaps(busy, lo, hi):
            lab = _label(g, labels)
            idle[lab] = idle.get(lab, 0.0) + (g[1] - g[0]) / 1e9
        coll = [(a, b) for a, b, nm in ops if COLLECTIVE.search(nm)]
        comp = union((a, b) for a, b, nm in ops if not COLLECTIVE.search(nm))
        exposed.append(total(subtract(coll, comp)) / 1e9)
    exposed.sort()
    mid = exposed[len(exposed) // 2] if len(exposed) % 2 else (
        exposed[len(exposed) // 2 - 1] + exposed[len(exposed) // 2]) / 2
    window_s = (hi - lo) / 1e9
    busy_s /= n
    by_time = lambda d: sorted(([k, v / n] for k, v in d.items()), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": n,
        "device_ops": by_time(op_time),
        "idle_gaps": by_time(idle),
        "exposed_collective_s": mid / steps if steps else mid,
    }
