"""Device time by layer scope and host time by program span, over the
traced slice.

The program runs its lane tick's model call, noise draw and step under
the named scopes ``unet`` (with ``attn`` for self-attention inside it),
``noise`` and ``step``; the scopes reach the ``op_name`` metadata of the
compiled ops.  A TPU profile's op events carry only the HLO instruction
(name, shapes, operands), so :func:`op_names` reads instruction ->
``op_name`` from a program's compiled HLO text
(``jax.jit(f).lower(...).compile().as_text()``) and :func:`extract` gives
each op of that program its scope path.  With its tracer on, the program
also annotates its host-loop spans as ``serve.<phase>`` on the profile's
host plane; :func:`extract` keeps them beside the harness's ``bench:``
annotations.

The compact trace :func:`extract` returns is
:func:`benchlib.trace.extract`'s, plus ``op_scopes`` on each device
plane: the scope path of each op of its ``XLA Ops`` line (``"unet/attn"``;
``""`` for an op under no scope; None where no compiled text named the
op).  :func:`reduce` works on that dict alone, so it is tested on a
recorded one (``bench/tests/data``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from benchlib.trace import (CONTROL, HOST_PREFIX, MODULES_LINE, OPS_LINE,
                            SLICE, _clip, _union, base_name, compact)
from benchlib.trace import label_gap as bench_label_gap

SCOPES = ("unet", "attn", "noise", "step")
PROGRAM_PREFIX = "serve."
UNMAPPED = "?"
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$', re.M)
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def scope_path(op_name: str) -> str:
    """The layer scopes named in an op's ``op_name``, outermost first:
    ``jit(window)/while/body/unet/attn/dot_general`` -> ``unet/attn``."""
    return "/".join(p for p in op_name.split("/") if p in SCOPES)


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction of a compiled
    HLO text (``""`` for one the compiler added without metadata)."""
    out = {}
    for m in _INSTR.finditer(hlo_text):
        op = _OP_NAME.search(m.group(2))
        out[m.group(1)] = op.group(1) if op else ""
    return out


def _instruction(op_event_name: str) -> str:
    """``%fusion.15 = f32[..] fusion(..)`` -> ``fusion.15``."""
    return op_event_name.split(" = ", 1)[0].strip().lstrip("%")


def _scopes(ops: List[list], mods: List[tuple],
            hlo: Dict[str, List[Dict[str, str]]]) -> List[Optional[str]]:
    """Each op's scope path, from the compiled text of the program run
    that holds it; of a program's texts (one per shape), the one naming
    most of the run's instructions."""
    starts = [m[0] for m in mods]
    runs: Dict[str, List[int]] = {}
    for k, (name, s, _) in enumerate(ops):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1]:
            runs.setdefault(mods[i][2], []).append(k)
    out: List[Optional[str]] = [None] * len(ops)
    for run, idx in runs.items():
        instrs = [_instruction(ops[k][0]) for k in idx]
        maps = hlo.get(base_name(run), [])
        if not maps:
            continue
        best = max(maps, key=lambda m: sum(i in m for i in instrs))
        for k, ins in zip(idx, instrs):
            if ins in best:
                out[k] = scope_path(best[ins])
    return out


def extract(profile_dir, hlo_texts: Dict[str, List[str]]) -> dict:
    """The compact trace of the newest profile under ``profile_dir``, with
    the program's host spans and each op's scope path.  ``hlo_texts``
    maps a program's base name (``jit_window``) to the compiled HLO texts
    of its runs."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(str(profile_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    hlo = {prog: [op_names(t) for t in texts]
           for prog, texts in hlo_texts.items()}
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            raw = {line.name: [[e.name, e.start_ns, e.duration_ns]
                               for e in line.events]
                   for line in plane.lines
                   if line.name in (MODULES_LINE, OPS_LINE)}
            lines = {}
            if MODULES_LINE in raw:
                lines[MODULES_LINE] = raw[MODULES_LINE]
            dev = {"name": plane.name, "lines": lines}
            if OPS_LINE in raw:
                ops = raw[OPS_LINE]
                mods = sorted((s, s + d, n)
                              for n, s, d in raw.get(MODULES_LINE, []))
                dev["op_scopes"] = _scopes(ops, mods, hlo)
                lines[OPS_LINE] = [[compact(n), s, d] for n, s, d in ops]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events
                         if e.name.startswith((HOST_PREFIX, PROGRAM_PREFIX))]
    return {"devices": devices, "host": host}


def _bounds(trace: dict, planes) -> Tuple[float, float]:
    slices = [(s, s + d) for n, s, d in trace["host"] if n == SLICE]
    if slices:
        return slices[0]
    evs = [e for p in planes for e in p["lines"][OPS_LINE]]
    return min(s for _, s, _ in evs), max(s + d for _, s, d in evs)


def _spans(trace: dict, lo: float, hi: float) -> List[list]:
    """The program's host spans clipped to the slice, each as [name,
    start, end, depth, parent index], in start order (spans of one
    thread nest by time)."""
    spans = sorted((s, -d, n) for n, s, d in trace["host"]
                   if n.startswith(PROGRAM_PREFIX))
    out: List[list] = []
    stack: List[Tuple[int, float]] = []       # (index, unclipped end)
    for s, neg_d, n in spans:
        e = s - neg_d
        while stack and stack[-1][1] <= s:
            stack.pop()
        parent = stack[-1][0] if stack else -1
        a, b = _clip(s, e, lo, hi)
        out.append([n, a, max(a, b), len(stack), parent])
        stack.append((len(out) - 1, e))
    return out


def _overlap(xs: List[List[float]], ys: List[List[float]]) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        total += max(0.0, min(xs[i][1], ys[j][1]) - max(xs[i][0], ys[j][0]))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def label_gap(spans: List[list], host: List[list], start: float,
              end: float) -> str:
    """What the host was doing in an idle gap: the program span that
    holds most of it as its innermost span, else the harness's own
    annotation (:func:`benchlib.trace.label_gap`)."""
    inside: Dict[str, float] = {}
    cover = [sp for sp in spans if sp[1] < end and sp[2] > start]
    if cover:
        cuts = sorted({start, end} | {x for sp in cover for x in sp[1:3]
                                      if start < x < end})
        for a, b in zip(cuts, cuts[1:]):
            held = [sp for sp in cover if sp[1] <= a and b <= sp[2]]
            if held:
                name = max(held, key=lambda sp: sp[3])[0]
                inside[name] = inside.get(name, 0.0) + (b - a)
    if inside:
        return "host: " + max(inside.items(), key=lambda kv: kv[1])[0]
    return bench_label_gap(host, start, end)


def reduce(trace: dict, top: int = 10) -> dict:
    """Over the traced slice (the host span ``bench:slice``), averaged
    over the device planes that ran work:

    ``device_s``  seconds of device ops by program and scope path
                  (``""``: no scope, ``"?"``: no compiled text named the
                  op), leaving out the while and conditional ops that
                  span their bodies;
    ``host``      per program span: total and self seconds and calls;
    ``nested_s``  seconds of each span under each enclosing span name,
                  keyed ``"<outer>><inner>"``;
    ``idle_s``    device idle time, and ``idle_in_span_s`` the part of
                  it a program span covers;
    ``idle_gaps`` the longest idle gaps, each named by :func:`label_gap`.
    """
    planes = [p for p in trace["devices"] if p["lines"].get(OPS_LINE)]
    if not planes:
        return {}
    lo, hi = _bounds(trace, planes)
    spans = _spans(trace, lo, hi)
    covered = _union([(sp[1], sp[2]) for sp in spans])
    device: Dict[str, Dict[str, float]] = {}
    idle_ns = idle_in_span_ns = 0.0
    gaps: List[Tuple[float, float, float]] = []
    for p in planes:
        mods = sorted((s, s + d, base_name(n))
                      for n, s, d in p["lines"].get(MODULES_LINE, []))
        starts = [m[0] for m in mods]
        op_scopes = p.get("op_scopes") or [None] * len(p["lines"][OPS_LINE])
        ops = []
        for (name, s, d), scope in zip(p["lines"][OPS_LINE], op_scopes):
            a, b = _clip(s, s + d, lo, hi)
            if b <= a:
                continue
            ops.append((a, b))
            if compact(name).startswith(CONTROL):
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "none"
            key = UNMAPPED if scope is None else scope
            by_scope = device.setdefault(prog, {})
            by_scope[key] = by_scope.get(key, 0.0) + (b - a)
        edges = [lo] + [x for iv in _union(ops) for x in iv] + [hi]
        idle = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps += [(b - a, a, b) for a, b in idle]
        idle_ns += sum(b - a for a, b in idle)
        idle_in_span_ns += _overlap(idle, covered)
    host: Dict[str, Dict[str, float]] = {}
    nested: Dict[str, float] = {}
    for name, a, b, _, parent in spans:
        h = host.setdefault(name, {"s": 0.0, "self_s": 0.0, "calls": 0})
        h["s"] += (b - a) * 1e-9
        h["self_s"] += (b - a) * 1e-9
        h["calls"] += 1
        if parent >= 0:
            host[spans[parent][0]]["self_s"] -= (b - a) * 1e-9
        outer, j = set(), parent
        while j >= 0:
            outer.add(spans[j][0])
            j = spans[j][4]
        for o in outer:
            key = f"{o}>{name}"
            nested[key] = nested.get(key, 0.0) + (b - a) * 1e-9
    n = len(planes)
    gaps.sort(reverse=True)
    return {
        "device_s": {prog: {k: v * 1e-9 / n for k, v in sorted(sc.items())}
                     for prog, sc in sorted(device.items())},
        "host": dict(sorted(host.items())),
        "nested_s": dict(sorted(nested.items())),
        "idle_s": idle_ns * 1e-9 / n,
        "idle_in_span_s": idle_in_span_ns * 1e-9 / n,
        "idle_gaps": [[label_gap(spans, trace["host"], s, e), g * 1e-9]
                      for g, s, e in gaps[:top]],
    }


def scope_time(reduced: dict, program: str, scope: str) -> Optional[float]:
    """Device seconds of ``program`` ops under ``scope`` (inner scopes
    included); None where no op of the program carries the scope."""
    by_scope = reduced.get("device_s", {}).get(program, {})
    hits = [v for k, v in by_scope.items()
            if k == scope or k.startswith(scope + "/")]
    return sum(hits) if hits else None
