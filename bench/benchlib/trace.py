"""Reduction of a profiler trace to the device's busy time, program and
kernel times, idle gaps and the breakdown.

:func:`extract` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into
a small dict: the device planes' module and op events (ops by their
:func:`compact` names), and the host events the harness annotated (names
starting with ``bench:``).
:func:`reduce` works on that dict alone, so it is tested on a recorded
one (``bench/tests/data``).  Times are nanoseconds on the profiler's
clock, which the host and device planes share.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"
SLICE = "bench:slice"
KERNEL = re.compile(r"tpu_custom_call")   # a Pallas kernel's op
TOP = 10                                  # entries of each breakdown list


def extract(profile_dir: str) -> dict:
    """The compact trace of the newest profile under ``profile_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    lines[line.name] = [[e.name, e.start_ns, e.duration_ns]
                                        for e in line.events]
                elif line.name == OPS_LINE:
                    lines[line.name] = [[compact(e.name), e.start_ns,
                                         e.duration_ns] for e in line.events]
            devices.append({"name": plane.name, "lines": lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    return {"devices": devices, "host": host}


def base_name(name: str) -> str:
    """A program or op name without its run id and numeric suffix:
    ``jit_window(12)`` -> ``jit_window``; an op event, named by its HLO
    text ``%fusion.123 = f32[..] fusion(..)``, -> ``fusion``, and a custom
    call -> ``custom-call[<target>]``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    head = re.sub(r"\(.*\)$", "", head)
    head = re.sub(r"[.:]\d+$", "", head)
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{head}[{target.group(1)}]" if target else head


CONTROL = "control:"


def compact(name: str) -> str:
    """An op's short name: :func:`base_name`, marked ``control:`` for a
    while or conditional op, which spans the ops of its body.  Applying it
    twice changes nothing."""
    if name.startswith(CONTROL):
        return name
    loop = re.search(r"\b(while|conditional)\(", name) is not None
    kind = re.search(r"\bkind=(k\w+)", name)
    return ((CONTROL if loop else "") + base_name(name)
            + (f"{{{kind.group(1)}}}" if kind else ""))


def _clip(start, end, lo, hi) -> Tuple[float, float]:
    return max(start, lo), min(end, hi)


def _union(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(trace: dict) -> dict:
    """Busy and program times over the traced slice (the host span
    ``bench:slice``), averaged over the device planes that ran work.

    Returns ``window_s``, ``busy_s``, ``module_s`` (seconds per program,
    by base name), ``kernel`` (time and calls of the Pallas kernels, by
    enclosing program),
    ``device_ops`` (op time by program and op kind, leaving out the
    while and conditional ops that span their bodies) and ``idle_gaps``
    (the breakdown's two lists)."""
    slices = [(s, s + d) for n, s, d in trace["host"] if n == SLICE]
    planes = [p for p in trace["devices"] if p["lines"].get(OPS_LINE)]
    if not planes:
        return {}
    if slices:
        lo, hi = slices[0]
    else:
        evs = [e for p in planes for e in p["lines"][OPS_LINE]]
        lo = min(s for _, s, _ in evs)
        hi = max(s + d for _, s, d in evs)
    window_ns = hi - lo
    busy_ns = 0.0
    module_ns: Dict[str, float] = {}
    kernel: Dict[str, Dict[str, float]] = {}
    op_ns: Dict[str, float] = {}
    gaps: List[Tuple[float, float, float]] = []
    for p in planes:
        mods = sorted((s, s + d, base_name(n))
                      for n, s, d in p["lines"].get(MODULES_LINE, []))
        starts = [m[0] for m in mods]

        def owner(t):
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t < mods[i][1] else "none"
        for s, e, name in mods:
            a, b = _clip(s, e, lo, hi)
            if b > a:
                module_ns[name] = module_ns.get(name, 0.0) + (b - a)
        ops = []
        for name, s, d in p["lines"][OPS_LINE]:
            a, b = _clip(s, s + d, lo, hi)
            if b <= a:
                continue
            ops.append((a, b))
            prog = owner(s)
            name = compact(name)
            if not name.startswith(CONTROL):
                key = f"{prog}/{name}"
                op_ns[key] = op_ns.get(key, 0.0) + (b - a)
            if KERNEL.search(name):
                k = kernel.setdefault(prog, {"ns": 0.0, "calls": 0})
                k["ns"] += b - a
                k["calls"] += 1
        busy = _union(ops)
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                gaps.append((edges[i + 1] - edges[i], edges[i], edges[i + 1]))
    n = len(planes)
    gaps.sort(reverse=True)
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "module_s": {k: v * 1e-9 / n for k, v in module_ns.items()},
        "kernel": {k: {"s": v["ns"] * 1e-9 / n, "calls": v["calls"] / n}
                   for k, v in kernel.items()},
        "device_ops": [[k, v * 1e-9 / n] for k, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label_gap(trace["host"], s, e), g * 1e-9]
                      for g, s, e in gaps[:TOP]],
    }


def label_gap(host: List[list], start: float, end: float) -> str:
    """What the host was doing in an idle gap: the annotated host span
    that overlaps it most, else unannotated engine host work."""
    best, label = 0.0, "host: engine bookkeeping (not annotated)"
    for name, s, d in host:
        if name == SLICE:
            continue
        ov = min(end, s + d) - max(start, s)
        if ov > best:
            best, label = ov, f"host: {name[len(HOST_PREFIX):]}"
    return label


def module_time(reduced: dict, prefix: str) -> Optional[float]:
    """Seconds of the programs whose base name starts with ``prefix``."""
    hits = [v for k, v in reduced.get("module_s", {}).items()
            if k.startswith(prefix)]
    return sum(hits) if hits else None
