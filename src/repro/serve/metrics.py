"""Serving telemetry: per-request latency, tick utilization, FLOP split.

The engine reports one event per admission/retirement plus one utilization
sample per tick; :meth:`ServeMetrics.summary` folds them into the record
written to ``results/BENCH_serve.json`` (requests/s, p50/p95 latency,
mean slot utilization, and the server/client FLOP accounting via
:func:`repro.core.collafuse.flops_split` — the paper's H2c energy proxy
applied to inference traffic).  When a client stack is served the summary
also carries :func:`finish_summary` — overlap-aware accounting for the
client segment (``finish_s``/``overlap_frac``/``finish_batches``), which
distinguishes the streamed finisher (client batches overlapped with
server scan windows) from the post-drain reference path.  Under a KID
admission gate the summary
grows an ``admission`` section (:func:`admission_summary`): action counts
and the served disclosure-KID histogram, with rejected requests excluded
from the FLOP accounting (they never ran a model call).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.collafuse import CutPlan, flops_split_steps
from repro.obs.registry import NULL_REGISTRY


class ServeMetrics:
    """Event sink for one engine run.

    ``registry`` (an :class:`repro.obs.MetricsRegistry`, default disabled)
    is the LIVE side: every event is additionally published into named
    instruments so a long-running engine is observable mid-run via the
    registry's JSON-lines snapshots, not only at :meth:`summary` time.
    """

    def __init__(self, capacity: int, registry=None):
        self.capacity = capacity
        self.registry = registry if registry is not None else NULL_REGISTRY
        self._admit: Dict[int, Dict] = {}       # req_id -> {tick, wall}
        self._retire: Dict[int, Dict] = {}
        self._util: List[float] = []            # active lanes / capacity
        self._t0: Optional[float] = None
        self._windows = 0                       # fused-dispatch count
        self._idle_ticks = 0                    # ticks skipped while empty
        self._lags: List[int] = []              # retire boundary - exact tick
        self._finish_batches = 0                # streamed client-finish calls
        self._finish_lanes = 0
        self._finish_lane_steps = 0             # padded lanes x fori bound
        self._finish_useful_lane_steps = 0      # each valid lane's K - cut
        # heterogeneous-traffic telemetry (on_window_mix): slot-ticks per
        # trajectory class, and slot-ticks that sat EMPTY while arrived
        # demand waited in the queue (fragmentation)
        self._occ_by_class: Dict[str, int] = {}
        self._frag_slot_ticks = 0
        self._mix_ticks = 0

    # ------------------------------------------------------------------
    def start(self) -> None:
        self._t0 = time.perf_counter()

    def _now(self) -> float:
        # auto-start on the first event: the old `self._t0 or 0.0`
        # fallback silently recorded ABSOLUTE perf_counter values (epoch
        # = process start) when start() was never called, poisoning every
        # wall-latency delta mixed with post-start events
        if self._t0 is None:
            self.start()
        return time.perf_counter() - self._t0

    def on_admit(self, req_id: int, tick: int) -> None:
        self._admit[req_id] = {"tick": tick, "wall": self._now()}
        self.registry.counter(
            "serve_admitted_total", "requests admitted into slots").inc()

    def on_retire(self, req_id: int, tick: int) -> None:
        self._retire[req_id] = {"tick": tick, "wall": self._now()}
        self.registry.counter(
            "serve_retired_total", "requests retired at the cut").inc()
        self.registry.histogram(
            "serve_latency_ticks", "admit->retire residency in ticks"
        ).observe(tick - self._admit[req_id]["tick"])

    def on_tick(self, active_lanes: int) -> None:
        self.on_window(active_lanes, 1)

    def on_window(self, active_lanes: int, ticks: int) -> None:
        """One fused dispatch of ``ticks`` scan ticks with ``active_lanes``
        lanes live at the window start — the window-START occupancy
        APPROXIMATION (lanes finishing mid-window still count for the
        whole window).  The engine now reports exact per-tick counts via
        :meth:`on_window_exact`; this stays for callers without a done
        stack (and as the comparison baseline in tests)."""
        self._window_sampled(ticks)
        self._util.extend([active_lanes / max(self.capacity, 1)] * ticks)

    def on_window_exact(self, active_start: int, done_counts) -> None:
        """Exact per-tick occupancy for one fused window, recovered from
        the (k, slots) done stack the engine already syncs (no new device
        round-trip): ``done_counts[j]`` lanes latched AT window tick j, a
        lane is active THROUGH its finish tick inclusive, so the count at
        tick j is ``active_start`` minus the lanes finished strictly
        before j."""
        counts = np.asarray(done_counts, np.int64)
        assert int(counts.sum()) <= active_start, \
            f"{counts.sum()} lanes done in a window that started with " \
            f"{active_start} active"
        self._window_sampled(counts.size)
        retired_before = np.concatenate(([0], np.cumsum(counts[:-1])))
        act = active_start - retired_before
        self._util.extend((act / max(self.capacity, 1)).tolist())
        self.registry.gauge(
            "serve_active_lanes", "live lanes at the window's last tick"
        ).set(int(act[-1] - counts[-1]))

    def _window_sampled(self, ticks: int) -> None:
        self._windows += 1
        self.registry.counter("serve_windows_total",
                              "fused scan windows dispatched").inc()
        self.registry.counter("serve_ticks_total",
                              "scan ticks executed").inc(ticks)

    def on_window_mix(self, class_lanes: Dict[str, int], free: int,
                      starved: bool, ticks: int) -> None:
        """Per-window trajectory-class occupancy + fragmentation sample,
        reported by the engine at each dispatch: ``class_lanes`` maps a
        class label (``"<sampler>@<effective_cut>@<guidance w>"``) to its
        live lanes this window — a guided request contributes 2 lanes per
        image (its cond+uncond pair) but stays ONE request everywhere
        requests are counted — ``free`` is the empty slots, ``starved`` says
        whether ARRIVED demand was left waiting in the queue.  Free slots
        in a starved window are FRAGMENTATION — capacity the scheduler
        could not shape the queue into (ragged frees vs batch>1 heads);
        free slots with an empty queue are just low load and don't
        count.  Aggregated into ``fragmentation_frac`` and
        ``occupancy_by_class`` in :meth:`summary`."""
        for cls, lanes in class_lanes.items():
            self._occ_by_class[cls] = \
                self._occ_by_class.get(cls, 0) + lanes * ticks
        if starved and free > 0:
            self._frag_slot_ticks += free * ticks
        self._mix_ticks += ticks
        self.registry.gauge(
            "serve_fragmentation_free_lanes",
            "empty slots entering a window while arrived demand waits"
        ).set(free if starved else 0)

    def on_idle_gap(self, gap: int) -> None:
        """Ticks the engine SKIPPED because no lane was in flight (it
        jumps ``now`` to the next arrival instead of spinning) — recorded
        so the jump is visible in the summary instead of silent."""
        if gap > 0:
            self._idle_ticks += gap
            self.registry.counter(
                "serve_idle_ticks_total",
                "ticks skipped with no lane in flight").inc(gap)

    def on_finish_dispatch(self, n_requests: int, lanes: int,
                           lane_steps: int, useful_lane_steps: int,
                           programs: int) -> None:
        """One streamed client-finish wave dispatched (finish_mode=
        "stream"): ``n_requests`` freshly-retired requests, split by
        client into ``programs`` padded finish programs, handed to the
        device while server windows may still be in flight.
        ``lane_steps`` is what the wave computes (Σ over its programs of
        padded width x that program's step bound), ``useful_lane_steps``
        what its valid lanes need."""
        self._finish_batches += 1
        self._finish_lanes += lanes
        self._finish_lane_steps += lane_steps
        self._finish_useful_lane_steps += useful_lane_steps
        steps = self.registry.counter(
            "serve_finish_lane_steps_total",
            "client lane-steps of the finisher, dispatched (padding "
            "included) and useful", labels=("kind",))
        steps.labels(kind="dispatched").inc(lane_steps)
        steps.labels(kind="useful").inc(useful_lane_steps)
        self.registry.counter(
            "serve_finish_batches_total",
            "streamed client-finish batches dispatched").inc()
        self.registry.counter(
            "serve_finish_programs_total",
            "per-client finish programs in the streamed batches").inc(
                programs)
        self.registry.counter(
            "serve_finish_lanes_total",
            "lanes handed to the streaming client finisher").inc(lanes)
        self.registry.histogram(
            "serve_finish_batch_requests",
            "requests per streamed client-finish batch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128)).observe(n_requests)

    def on_boundary_lag(self, lag: int) -> None:
        """Retirement happens at the scan-window boundary; ``lag`` is how
        many ticks earlier the lane actually reached its cut (exact finish
        read back from the per-tick done stack).  Bounded by
        ticks_per_dispatch - 1 by construction — asserted p100 in
        tests/test_serve.py."""
        self._lags.append(lag)
        self.registry.histogram(
            "serve_boundary_lag_ticks",
            "retire boundary minus exact finish tick, per lane",
            buckets=(0, 1, 2, 4, 8, 16, 32, 64)).observe(lag)

    # ------------------------------------------------------------------
    @property
    def ticks(self) -> int:
        return len(self._util)

    def latency_ticks(self, req_id: int) -> Optional[int]:
        """Server-segment residency: admission tick -> retirement tick."""
        if req_id not in self._retire:
            return None
        return self._retire[req_id]["tick"] - self._admit[req_id]["tick"]

    def summary(self, wall_s: float, T: int, flops_per_call: float,
                requests, steps_of: Optional[Callable] = None,
                decisions: Optional[Dict] = None,
                guided_of: Optional[Callable] = None) -> Dict:
        """Aggregate one run over ``requests`` (the completed Request
        objects) into the BENCH_serve.json record.

        ``steps_of(req) -> (n_server_steps, n_client_steps)`` supplies the
        per-request model-call counts — the engine passes its samplers'
        trajectory-relative split so strided (DDIM) requests are accounted
        at what they actually cost; the default is the dense CutPlan split.

        ``guided_of(req) -> bool`` (default: nothing is guided) marks
        requests whose sampler runs classifier-free guidance: their SERVER
        segment is accounted at exactly 2× model FLOPs (the cond+uncond
        lane pair — see :func:`flops_split_steps`) while the request,
        image, and latency counts stay per-REQUEST: a guided pair is one
        request occupying two lane-ticks per tick, never two requests
        (unit-tested in tests/test_serve.py).

        ``decisions`` ({req_id: AdmissionDecision}, when the KID gate is
        on) adds the ``admission`` section (:func:`admission_summary`) and
        excludes REJECTED requests from the FLOP accounting — they never
        executed a model call.
        """
        decisions = decisions or {}

        def _served(r):
            d = decisions.get(r.req_id)
            return d is None or d.served

        lat_t = np.array([self.latency_ticks(r.req_id) for r in requests
                          if self.latency_ticks(r.req_id) is not None],
                         dtype=np.float64)
        lat_w = np.array([self._retire[r.req_id]["wall"] -
                          self._admit[r.req_id]["wall"]
                          for r in requests if r.req_id in self._retire],
                         dtype=np.float64)
        if steps_of is None:
            def steps_of(r):
                plan = CutPlan(T, r.cut_ratio)
                return plan.n_server_steps, plan.n_client_steps
        server_f = client_f = 0.0
        images = 0
        n_served = 0
        for r in requests:
            if not _served(r):
                continue
            n_served += 1
            n_srv, n_cli = steps_of(r)
            split = flops_split_steps(
                n_srv, n_cli, flops_per_call, r.batch,
                guided=bool(guided_of(r)) if guided_of is not None else False)
            server_f += split["server_flops"]
            client_f += split["client_flops"]
            # r.batch IMAGES regardless of guidance: the shadow (uncond)
            # lane of a guided pair never emits an image
            images += r.batch
        total = max(server_f + client_f, 1.0)
        pct = (lambda q: float(np.percentile(lat_t, q))) if lat_t.size \
            else (lambda q: 0.0)
        pctw = (lambda q: float(np.percentile(lat_w, q))) if lat_w.size \
            else (lambda q: 0.0)
        out = {
            "requests": len(requests),
            "served": n_served,
            "images": images,
            "ticks": self.ticks,
            "windows": self._windows,
            "ticks_per_s": self.ticks / max(wall_s, 1e-9),
            "idle_ticks": self._idle_ticks,
            # throughput counts SERVED requests only: rejected ones never
            # ran a model call (ungated, served == requests)
            "requests_per_s": n_served / max(wall_s, 1e-9),
            "images_per_s": images / max(wall_s, 1e-9),
            "latency_ticks_p50": pct(50),
            "latency_ticks_p95": pct(95),
            "latency_s_p50": pctw(50),
            "latency_s_p95": pctw(95),
            "utilization_mean": float(np.mean(self._util))
            if self._util else 0.0,
            "server_flops": server_f,
            "client_flops": client_f,
            "client_fraction": client_f / total,
        }
        if self._mix_ticks:
            # share of dispatched slot-ticks that sat empty while arrived
            # demand waited — 0.0 is fragmentation-proof packing
            out["fragmentation_frac"] = self._frag_slot_ticks / (
                self.capacity * self._mix_ticks)
            out["occupancy_by_class"] = dict(
                sorted(self._occ_by_class.items()))
        if self._finish_batches:
            out["finish_lane_steps"] = self._finish_lane_steps
            out["finish_useful_lane_steps"] = self._finish_useful_lane_steps
        if self._lags:
            lags = np.array(self._lags, np.float64)
            out["boundary_lag_mean"] = float(lags.mean())
            out["boundary_lag_p100"] = int(lags.max())
        if decisions:
            out["admission"] = admission_summary(decisions.values(),
                                                 registry=self.registry)
        return out


def finish_summary(mode: str, finish_s: float, tail_s: float = 0.0,
                   batches: int = 0, lanes: int = 0,
                   programs: int = 0) -> Dict:
    """Overlap-aware accounting for the client-finish segment, merged
    into the serve summary by the engine.

    ``finish_s`` is the TOTAL host time spent in the client-finish path
    (pack + dispatch + sync).  In ``stream`` mode most of it runs while
    server scan windows are still in flight; the only serialized part is
    ``tail_s`` — the drain after the last window retired — so
    ``overlap_frac = 1 - tail_s / finish_s``.  In ``drain`` mode the
    whole segment runs after the server loop (``overlap_frac = 0``) and
    the CALLER adds ``finish_s`` to the wall clock; in stream mode the
    loop timer already covers the finish work, so throughput derived
    from that single wall never double-counts."""
    assert mode in ("stream", "drain"), mode
    if mode == "drain":
        overlap = 0.0
        tail_s = finish_s
    else:
        overlap = 1.0 - tail_s / finish_s if finish_s > 1e-12 else 1.0
    return {
        "finish_mode": mode,
        "finish_s": finish_s,
        "finish_tail_s": tail_s,
        "overlap_frac": float(min(1.0, max(0.0, overlap))),
        "finish_batches": batches,
        "finish_programs": programs,
        "finish_lanes": lanes,
    }


def admission_summary(decisions, bins: int = 8, registry=None) -> Dict:
    """Fold AdmissionDecisions into a JSON-able record: action counts plus
    a histogram of the SERVED disclosure KIDs (bumped requests included) —
    the online guarantee "no served request discloses below the floor"
    made inspectable in ``results/BENCH_privacy.json``.

    On a rejects-only iterable the ``disclosure_kid`` key is ABSENT (no
    served request has a disclosure) — renderers must treat it as
    optional (``benchmarks.report.privacy_table`` does; regression-tested
    in tests/test_obs.py).

    ``registry`` (optional :class:`repro.obs.MetricsRegistry`) receives
    the per-action counts as ``serve_admission_actions_total{action=}``.
    """
    ds = list(decisions)
    served = [d for d in ds if d.served]
    kids = np.array([d.kid for d in served], np.float64)
    rec = {
        "min_kid": ds[0].min_kid if ds else 0.0,
        "admitted": sum(1 for d in ds if d.action == "admit"),
        "bumped": sum(1 for d in ds if d.action == "bump"),
        "rejected": sum(1 for d in ds if d.action == "reject"),
    }
    if registry is not None and registry:
        actions = registry.counter("serve_admission_actions_total",
                                   "admission gate outcomes",
                                   labels=("action",))
        for act in ("admit", "bump", "reject"):
            actions.labels(action=act).inc(
                sum(1 for d in ds if d.action == act))
    if kids.size:
        counts, edges = np.histogram(kids, bins=bins)
        rec["disclosure_kid"] = {
            "min": float(kids.min()),
            "mean": float(kids.mean()),
            "max": float(kids.max()),
            "hist_counts": [int(c) for c in counts],
            "hist_edges": [float(e) for e in edges],
        }
    return rec
