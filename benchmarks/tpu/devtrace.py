"""From the profiler's trace to a compact record, and from that to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps the
traced window only: for each device, the events of its ops line, and on the
host, the harness's own ``bench/...`` annotations.  The compact record is a
plain dict that JSON holds, so tests can check every reduction below on a
recorded trace without a chip.

Record layout (times in ns from the start of the window)::

    {"window_ns": int,
     "devices": {"0": [[name, start, duration], ...], ...},
     "types": {name: result type, ...},
     "host": [[name, start, duration], ...]}

``name`` is the HLO instruction's name (``fusion.4``), as the compiled
module's text names it, and ``types`` keeps its result's type for the
breakdown.  The TPU's ops events carry no name scope (their stats are
device times alone), so a reader maps names to scopes or kernels through
the compiled module's text (``scoped_instructions``,
``kernel_instructions``).
"""
from __future__ import annotations

import base64
import binascii
import glob
import gzip
import json
import os
import re
from collections import defaultdict

#: the line of a TPU device plane that holds one event per executed HLO op
OPS_LINE = "XLA Ops"
#: the event a device plane gets where the profiler dropped what followed,
#: to keep the trace under its 2 GB limit
DROPPED = "Trace Buffers Dropped"
#: the harness's host span around the measured window
WINDOW_SPAN = "bench/window"
HOST_PREFIX = "bench/"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: a TPU ops-line event is named by its HLO instruction's text,
#: ``%fusion.4 = bf16[8,128]{1,0} fusion(...), kind=kLoop, calls=...``
_HLO_TEXT = re.compile(r"^%?([\w.\-]+) = (\(?[a-z][a-z0-9]*\[[^\]]*\])")


def op_name(name: str):
    """(instruction name, its first result's type) of an ops-line event; the
    type is "" where the event is named by the instruction alone."""
    m = _HLO_TEXT.match(name)
    return (m.group(1), m.group(2).lstrip("(")) if m else (name, "")


def load(logdir: str) -> dict:
    """The compact record of the newest trace under ``logdir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    host, device_planes = [], {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            device_planes[m.group(1)] = plane
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIX):
                    host.append([e.name, float(e.start_ns),
                                 float(e.duration_ns)])
    windows = [h for h in host if h[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    t0, dur = windows[-1][1], windows[-1][2]
    t1 = t0 + dur
    devices, types = {}, {}
    for idx, plane in device_planes.items():
        evs = []
        for line in plane.lines:
            for e in line.events:
                s, d = float(e.start_ns), float(e.duration_ns)
                if s + d <= t0 or s >= t1:
                    continue
                if e.name == DROPPED:
                    raise ValueError(f"the profiler dropped device {idx}'s "
                                     f"events from {(s - t0) / 1e9:.3f} s "
                                     f"into the window: trace a shorter one")
                if line.name != OPS_LINE:
                    continue
                name, typ = op_name(e.name)
                if typ:
                    types[name] = typ
                evs.append([name, round(s - t0), round(d)])
        devices[idx] = evs
    return {"window_ns": round(dur),
            "devices": devices,
            "types": types,
            "host": [[n, round(s - t0), round(d)] for n, s, d in host
                     if s + d > t0 and s < t1]}


def save(record: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(record, f, separators=(",", ":"))


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def _union(intervals, lo: float, hi: float):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events, window_ns: float) -> float:
    """Time in which at least one op ran, within the window."""
    return sum(e - s for s, e in _union(((ev[1], ev[1] + ev[2])
                                         for ev in events), 0, window_ns))


def busy_s(record: dict) -> float:
    """Busy seconds averaged over the traced devices."""
    devs = record["devices"]
    if not devs:
        return 0.0
    return sum(busy_ns(evs, record["window_ns"])
               for evs in devs.values()) / len(devs) / 1e9


def idle_share(record: dict) -> float:
    return 1.0 - busy_s(record) / (record["window_ns"] / 1e9)


def op_seconds(record: dict, match) -> float:
    """Device seconds, averaged over devices, of ops for which
    ``match(name)`` holds.  Ops nest (a loop holds its body's ops),
    so a match's time is the union of its events."""
    devs = record["devices"]
    if not devs:
        return 0.0
    total = 0.0
    for evs in devs.values():
        total += busy_ns([e for e in evs if match(e[0])],
                         record["window_ns"])
    return total / len(devs) / 1e9


def top_ops(record: dict, n: int = 10):
    """[["op name result type", device seconds summed over devices /
    devices], ...], largest first."""
    devs, types = record["devices"], record.get("types", {})
    acc = defaultdict(float)
    for evs in devs.values():
        for name, _, d in evs:
            acc[name] += d
    k = max(len(devs), 1)
    return [[f"{name} {types[name]}" if name in types else name, t / k / 1e9]
            for name, t in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(record: dict, n: int = 10):
    """Idle time of device 0 summed by the innermost host span it fell in,
    [[span name, seconds], ...], largest first."""
    devs = record["devices"]
    if not devs:
        return []
    evs = devs[min(devs, key=int)]
    w = record["window_ns"]
    busy = _union(((e[1], e[1] + e[2]) for e in evs), 0, w)
    gaps, t = [], 0.0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if t < w:
        gaps.append((t, w))
    spans = [h for h in record["host"] if h[0] != WINDOW_SPAN]
    acc = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        inside = [h for h in spans if h[1] <= mid < h[1] + h[2]]
        label = min(inside, key=lambda h: h[2])[0] if inside else "host: other"
        acc[label] += e - s
    return [[k, v / 1e9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            [:n]]


# ---------------------------------------------------------------------------
# HLO scopes: which ops of a compiled program came from a named scope
# ---------------------------------------------------------------------------

_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")  # fusions, not loop bodies
_OPNAME = re.compile(r'op_name="([^"]*)"')


def scoped_instructions(hlo_text: str, scope: str) -> set:
    """Names of the instructions of an optimized HLO module that carry
    ``scope`` in their op_name, directly or in a computation they call."""
    comp_names = defaultdict(set)     # computation -> op_names inside it
    calls = defaultdict(set)          # instruction -> computations it calls
    own = {}                          # instruction -> its op_name
    current = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and "=" not in line.split("(")[0]:
            current = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OPNAME.search(line)
        if op:
            own[name] = op.group(1)
            if current:
                comp_names[current].add(op.group(1))
        for c in _CALLS.findall(line):
            calls[name].add(c)

    def has(comp, seen):
        if comp in seen:
            return False
        seen.add(comp)
        return any(scope in o for o in comp_names.get(comp, ()))

    return {name for name in set(own) | set(calls)
            if scope in own.get(name, "")
            or any(has(c, set()) for c in calls.get(name, ()))}


_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]*)"')


def kernel_instructions(hlo_text: str, kernel: str) -> set:
    """Names of the Pallas calls (``tpu_custom_call`` instructions) of an
    optimized HLO module whose kernel is the function ``kernel``.  XLA names
    such an instruction after the transformations around the call
    (``jvp__.1``); the kernel's own name is kept only in the source
    locations of its serialized body."""
    key = kernel.encode()
    out = set()
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m, body = _INSTR.match(line), _BODY.search(line)
        if not (m and body):
            continue
        try:
            if key in base64.b64decode(body.group(1)):
                out.add(m.group(1))
        except binascii.Error:
            continue
    return out
