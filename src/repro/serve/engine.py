"""Continuous-batching split-inference engine for the CollaFuse server.

The paper's deployment story (§3, Fig. 1-2) is shared server-side
inference: each client draws x_T, the server runs the expensive first
(1-c)·T denoising steps, and x_{t_split} crosses back for cheap local
finishing.  Serving that to many concurrent clients one ``split_sample``
call per request costs O(requests) dispatch chains.  This engine is the
diffusion analogue of LLM continuous batching:

* Generation requests (heterogeneous cut-ratios, batch sizes, arrival
  ticks, SAMPLERS) queue in a scheduler and are admitted into a
  fixed-capacity array of SLOTS, one image ("lane") per slot.
* Every slot walks a TRAJECTORY (``repro.diffusion.sampler``) — the dense
  {T..1} DDPM chain or a strided K-step DDIM subsequence, chosen per
  request from the engine's registered sampler menu.  Per-slot counters
  are trajectory POSITIONS, not raw timesteps.
* Every DISPATCH runs ``ticks_per_dispatch`` masked trajectory ticks
  under ONE ``lax.scan`` — the k-tick fused window.  Each tick steps all
  live lanes (per-lane column gather into the concatenated sampler
  tables, one ``StepBackend`` program); a lane reaching its cut position
  mid-window latches: its carry (x, pos, key) is a bitwise fixed point of
  :func:`repro.diffusion.backend.make_lane_tick`, so retiring at the scan
  BOUNDARY reads the exact cut tensor at any k.  The scan emits a
  (k, slots) per-tick done stack, from which the host recovers each
  lane's exact finish tick for latency accounting.
* The host loop is DOUBLE-BUFFERED (``async_depth``): window N+1 is
  dispatched while window N's done-mask and retired x are still in
  flight — JAX's async dispatch overlaps the host's retire/refill
  bookkeeping with device compute; the loop only blocks on the OLDEST
  in-flight window once the pipeline is full.  Admission and retirement
  happen at window boundaries only (``scheduler.select_window``).
* POD MODE (``hosts`` > 1): slots are partitioned into contiguous
  per-host blocks (``sharding.lane_owners``, aligned with how
  ``sharding.slot_specs`` shards the slot axis over ``data``), every
  process replicates the deterministic scheduler/bookkeeping loop over
  one shared queue, the done stack is constrained REPLICATED
  (``sharding.gathered_sharding``) so every host reads it locally, and
  each host materializes the cut tensors of its OWNED lanes only
  (``Completion.owned`` marks which rows this host holds).
* A client-segment finisher completes the remaining trajectory positions
  for every emitted image under its client's private model, one program
  per (step class, client) — the same shared lane tick under
  ``fori_loop``.  By default it
  STREAMS (``finish_mode="stream"``): at each window boundary the
  requests whose last lane just retired are packed and dispatched
  asynchronously while the next server scan window is already in flight,
  double-buffered like the server pipeline (``finish_async_depth``) —
  bitwise identical to the post-drain reference pass
  (``finish_mode="drain"``), proven per-run by the exported trace's
  interleaved ``dispatch``/``client_finish_dispatch`` spans.

Key discipline: lane i of a request uses ``fold_in(req.key, i)`` split
into (k_init, k_srv, k_cli) — see :func:`repro.core.collafuse.lane_keys` —
and within a segment follows ``sample_range``'s ``k, k_n = split(k)`` chain
exactly, so every lane is replayed bit-for-bit in key space by
:func:`repro.core.collafuse.split_sample_lane` with the same sampler.
Because lane numerics depend ONLY on that key chain (never on slot index,
tick number, or neighbouring lanes), completions are bitwise invariant
under ``ticks_per_dispatch`` and ``async_depth`` — gated in
``benchmarks.run --only pod_ticks`` and tests/test_serve.py.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import collafuse
from repro.core.collafuse import CutPlan
from repro.diffusion.backend import (GUIDANCE_ROW, N_TABLE_ROWS, BackendLike,
                                     LaneSharded, get_backend,
                                     make_lane_tick)
from repro.diffusion.sampler import Sampler, assert_same_menu, default_samplers
from repro.diffusion.schedule import DiffusionSchedule
from repro.obs import NULL_OBS, Observability, ObsConfig, resolve_obs
from repro.serve.admission import AdmissionDecision, AdmissionPolicy
from repro.serve.metrics import ServeMetrics, finish_summary
from repro.serve.scheduler import FIFOScheduler, Request


@dataclasses.dataclass
class Completion:
    """One finished request: the disclosed tensor and (after the client
    finisher) the final images."""

    request: Request
    x_mid: np.ndarray                  # [batch, H, W, C] at the cut
    admit_tick: int
    retire_tick: int                   # scan-window boundary the lane
    #                                    retired at (== exact finish tick
    #                                    when ticks_per_dispatch == 1)
    k_cli: Optional[np.ndarray] = None  # [batch, 2] client-segment keys
    x0: Optional[np.ndarray] = None    # filled by the client finish
    client_finished: bool = False      # did serve() run the client segment?
    owned: Optional[np.ndarray] = None  # [batch] bool: x_mid rows THIS host
    #                                     materialized (all True off-pod)


@dataclasses.dataclass
class ServeResult:
    completions: Dict[int, Completion]
    summary: Dict
    wall_s: float
    # one AdmissionDecision per request when a KID gate is configured
    # (empty ungated); rejected requests appear HERE and not in completions
    decisions: Dict[int, AdmissionDecision] = \
        dataclasses.field(default_factory=dict)
    # per-request lifecycle timelines ({req_id: [{stage, wall, tick?,
    # ...}]}) when the engine runs with an obs config; empty obs-off
    timelines: Dict[int, List[Dict]] = \
        dataclasses.field(default_factory=dict)

    @property
    def rejected(self) -> Dict[int, AdmissionDecision]:
        return {rid: d for rid, d in self.decisions.items() if not d.served}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Everything a :class:`ServeEngine` is, minus the server weights.

    ``ServeEngine(config, server_params)`` is the one constructor; the
    config is FROZEN and validated here, at construction time, so a
    misconfigured engine fails before it owns a queue:

    * ``image_shape`` is canonicalized to a tuple.
    * ``samplers`` (the trajectory menu requests name into; None = the
      dense DDPM chain) must be built for the engine's schedule ``T``.
    * ``admission`` (optional KID gate) must be calibrated for the same
      ``T``; the engine binds its server model + menu into the policy and
      shares it with the scheduler.
    * ``ticks_per_dispatch`` (k) is the fused ``lax.scan`` window depth:
      retire/refill happen at window boundaries only, so k trades up to
      k-1 ticks of per-request boundary latency for k fewer host
      round-trips per tick.  ``async_depth`` is the number of windows in
      flight: 1 = synchronous (block on each window), 2 = double-buffered
      (bookkeep window N while N+1 computes).  Neither changes completion
      tensors — lanes latch bitwise at their cut inside the scan.
    * pod mode: ``hosts`` > 1 partitions the ``slots`` lanes into
      contiguous per-host ownership blocks (``slots % hosts == 0``);
      ``host_id`` defaults to ``jax.process_index()`` under a real
      ``jax.distributed`` launch and is overridable for simulated-host
      tests.  Single-host (``hosts == 1``): the engine owns every lane
      and ``host_id`` resolves to 0 whether left unset (``None``) or
      passed explicitly as 0 — the two are equivalent by an EXPLICIT
      ``None`` check, not truthiness, so an explicit ``host_id=0`` is
      honoured as a deliberate choice rather than conflated with
      "unset" (any other value fails validation against ``hosts``).
    * ``finish_mode`` picks how :meth:`ServeEngine.serve` runs the
      client segment when a ``client_stack`` is supplied: ``"stream"``
      (default) hands freshly-retired requests to an async finish
      dispatcher at each window boundary so client batches compute
      WHILE later server scan windows are in flight;``"drain"`` is the
      reference path — one monolithic finish pass after the server
      queue drains.  Both are bitwise identical per lane (numerics
      depend only on the key chain, never dispatch timing — gated in
      ``benchmarks.run --only finisher_overlap``).
      ``finish_async_depth`` is the finish pipeline's double-buffer
      depth, the exact analogue of ``async_depth``: 1 syncs each finish
      batch at the boundary that dispatched it, 2 keeps one batch in
      flight while the next server window computes.
    * ``num_classes`` > 0 switches the engine CONDITIONAL: ``apply_fn``
      takes ``(params, x, t, y)`` (y = int32 class labels; index
      ``num_classes`` is the null label) and requests may name GUIDED
      samplers (``make_sampler(..., guidance=w)``).  A guided request
      occupies a cond+uncond lane PAIR per image — both lanes ride the
      same model dispatch and fused step (the ε̂-combine happens in the
      backend's ``guided_masked_index_step``), so mixed guided/unguided
      traffic stays ONE program.  ``num_classes == 0`` (default) keeps
      the classic 3-arg convention and rejects guided menu entries.
    * ``spare_columns`` preallocates extra columns in the engine's
      concatenated coefficient table (plus matching spare menu rows) so
      :meth:`ServeEngine.register_sampler` can write an AD-HOC
      trajectory's (c_eps, ar, σ, keep) coefficients into them at run
      time with one device scatter — no retrace of any jitted program
      (the fused kernel already gathers per-lane columns; the menu
      arrays are traced arguments with construction-fixed shapes).
      0 (default) disables dynamic registration.
    """

    sched: DiffusionSchedule
    apply_fn: Callable
    image_shape: Any
    slots: int = 32
    scheduler: Any = None
    clip: float = 3.0
    step_backend: BackendLike = None
    mesh: Any = None
    samplers: Optional[Dict[str, Sampler]] = None
    admission: Optional[AdmissionPolicy] = None
    flops_per_call: Optional[float] = None
    ticks_per_dispatch: int = 1
    async_depth: int = 1
    hosts: int = 1
    host_id: Optional[int] = None
    finish_mode: str = "stream"
    finish_async_depth: int = 1
    spare_columns: int = 0
    num_classes: int = 0
    # observability: None (default, zero-cost off), an ObsConfig, or a
    # shared Observability instance (e.g. one bundle for engine + trainer)
    obs: Any = None

    def __post_init__(self):
        if self.obs is not None:
            assert isinstance(self.obs, (ObsConfig, Observability)) \
                or self.obs is NULL_OBS, \
                f"obs must be None, ObsConfig or Observability; got " \
                f"{type(self.obs).__name__}"
        object.__setattr__(self, "image_shape", tuple(self.image_shape))
        assert self.slots >= 1, self.slots
        assert 1 <= self.ticks_per_dispatch <= 512, \
            f"ticks_per_dispatch={self.ticks_per_dispatch} outside [1, 512]" \
            " — the scan window must be positive and bounded (unrolled " \
            "retire latency and liveness bounds scale with it)"
        assert 1 <= self.async_depth <= 32, \
            f"async_depth={self.async_depth} outside [1, 32]"
        assert self.finish_mode in ("stream", "drain"), \
            f"finish_mode={self.finish_mode!r} not in ('stream', 'drain')"
        assert 1 <= self.finish_async_depth <= 32, \
            f"finish_async_depth={self.finish_async_depth} outside [1, 32]"
        assert 0 <= self.spare_columns <= 4096, \
            f"spare_columns={self.spare_columns} outside [0, 4096] — " \
            "spare coefficient columns are preallocated device memory " \
            "(4 rows of float32 each plus a padded timestep row)"
        assert self.hosts >= 1, self.hosts
        assert self.slots % self.hosts == 0, \
            f"slots={self.slots} not divisible by hosts={self.hosts} — " \
            "lane ownership is contiguous equal blocks"
        assert self.num_classes >= 0, self.num_classes
        if self.samplers is not None and self.num_classes == 0:
            for name, s in self.samplers.items():
                assert not s.guided, \
                    f"sampler {name!r} is guided (w={s.w:g}) but " \
                    "num_classes == 0 — classifier-free guidance needs a " \
                    "conditional engine (EngineConfig(num_classes=N) and " \
                    "a 4-arg apply_fn)"
        if self.host_id is not None:
            assert 0 <= self.host_id < self.hosts, \
                f"host_id={self.host_id} outside [0, {self.hosts})"
        if self.samplers is not None:
            for name, s in self.samplers.items():
                assert s.trajectory.T == self.sched.T, \
                    f"sampler {name!r} built for T={s.trajectory.T}, " \
                    f"engine schedule has T={self.sched.T}"
        if self.admission is not None:
            assert self.admission.sched.T == self.sched.T, \
                f"admission policy calibrated for T=" \
                f"{self.admission.sched.T}, engine schedule has " \
                f"T={self.sched.T}"


def _device_ready(ref) -> bool:
    """True when an in-flight device array has finished computing — the
    non-blocking probe the finish pipeline uses to reap batches early.
    Arrays without ``is_ready`` (plain numpy in tests) count as ready."""
    probe = getattr(ref, "is_ready", None)
    return bool(probe()) if probe is not None else True


class _FinishPipeline:
    """Streaming client finisher (``finish_mode="stream"``): the client
    segment's double-buffered dispatch pipeline, the exact analogue of
    the server loop's ``pending`` deque.  At each window boundary the
    engine stages freshly-retired requests here (via the scheduler's
    ``on_retired`` hook) into per-CLASS buckets — class = (trajectory,
    cut), i.e. lanes that run the exact same number of client steps;
    :meth:`flush` COALESCES each bucket until roughly two server
    windows' worth of lanes are staged, then packs a WAVE from it and
    dispatches it ASYNCHRONOUSLY — the next server scan window is
    already in flight.  A wave is step-homogeneous AND split by client:
    :meth:`ServeEngine._pack_finish` runs one program per client present
    over that client's one-row param stack, so no model call is grouped
    across clients, each program is padded to its own client's pow-2
    width (two lanes at least), and its fori bound is its lanes' exact
    ``K - cut``.  The
    buckets are load-bearing precisely BECAUSE arrival is streamed:
    expensive lanes trickle in a few per window, so any policy that
    mixes classes per wave seeds nearly every wave with a fresh
    long-step lane and re-pays the global bound wave after wave.
    Waves are wide (``2 * slots`` lanes) because each finish dispatch
    also carries a fixed host-pack + program-launch cost that dwarfs a
    few lanes' compute: a per-boundary trickle of 2-4 lanes would be
    pure overhead.  Waves already in flight are reaped WITHOUT blocking
    as soon as the device reports every program of the wave ready; the
    host only blocks once ``finish_async_depth`` waves are in flight.
    :meth:`drain` closes the tail after the server queue empties, one
    class at a time; everything before that tail overlapped server
    compute, so the summary reports ``overlap_frac = 1 - tail_s /
    finish_s`` (:func:`repro.serve.metrics.finish_summary`).

    Bitwise identical to ``ServeEngine._finish_clients`` (the post-drain
    reference): per-lane finish numerics depend only on (param row,
    x_mid, pos, end, traj, key) — program composition, wave partition,
    coalescing cadence, pow-2 padding, and the shared fori bound are all
    masked/latched out — gated in ``benchmarks.run --only
    finisher_overlap``."""

    def __init__(self, engine: "ServeEngine", client_stack,
                 metrics: ServeMetrics):
        self._eng = engine
        self._stack = client_stack
        self._metrics = metrics
        self._depth = engine.finish_async_depth
        # wave granularity: ~two server windows' worth of lanes per
        # program — wide enough to amortize the per-dispatch fixed cost
        # (host pack + launch + sync), narrow enough that waves still
        # interleave with in-flight windows
        self._wave_lanes = max(1, 2 * engine.slots)
        # step-class buckets: (traj id, cut, K) -> list of completions.
        # The class is a REQUEST property (every lane of a request shares
        # its trajectory and cut), so buckets never split a completion.
        self._ready: Dict[tuple, List] = {}
        self._staged: Dict[tuple, int] = {}    # staged lanes per class
        # in-flight finish waves, oldest first: ([(x0 device ref,
        # placement) per program], dispatch tick)
        self._pending: collections.deque = collections.deque()
        self.batches = 0
        self.programs = 0
        self.lanes = 0
        self.host_s = 0.0    # total host time inside the finish path
        self.tail_s = 0.0    # the post-drain (non-overlapped) stretch

    def stage(self, comp: Completion) -> None:
        """Hand one fully-retired request to the pipeline (wired to the
        scheduler's retired-request hook); packed into a step-homogeneous
        wave once its class coalesces enough lanes at a flush."""
        r = comp.request
        cut = self._eng._effective_cut(r)
        K = self._eng._sampler_of(r).K
        key = (self._eng._traj_ids[r.sampler], cut, K)
        self._ready.setdefault(key, []).append(comp)
        self._staged[key] = self._staged.get(key, 0) + r.batch

    def _take_wave(self, key) -> List[Completion]:
        """Pop one wave off a class bucket (completion granular — the
        remainder stays staged for the next flush/drain)."""
        bucket, taken, lanes = self._ready[key], [], 0
        while bucket and lanes < self._wave_lanes:
            comp = bucket.pop()
            taken.append(comp)
            lanes += comp.request.batch
        if not bucket:
            del self._ready[key]
            del self._staged[key]
        else:
            self._staged[key] -= lanes
        return taken

    def _dispatch(self, comps: List[Completion], now: int) -> None:
        n_lanes = sum(c.request.batch for c in comps)
        with self._eng.obs.tracer.span(
                "client_finish_dispatch", tick=now, requests=len(comps),
                lanes=n_lanes):
            wave = self._eng._pack_finish(comps, self._stack, self._metrics)
            self._pending.append((wave, now))
        self.batches += 1
        self.programs += len(wave)
        self.lanes += n_lanes

    def _sync_oldest(self, now: int) -> None:
        wave, disp_tick = self._pending.popleft()
        with self._eng.obs.tracer.span(
                "client_finish_sync", tick=now, dispatch_tick=disp_tick,
                lanes=sum(len(p) for _, p in wave)):
            for x0_ref, placement in wave:
                self._eng._scatter_finish(x0_ref, placement)

    def _oldest_ready(self) -> bool:
        return all(_device_ready(ref) for ref, _ in self._pending[0][0])

    def flush(self, now: int, queue_drained: bool = False) -> None:
        """One boundary's hand-off: reap (without blocking) every
        in-flight wave the device has already finished, then dispatch a
        wave from every class bucket that coalesced one, and drain the
        pipeline down to ``depth - 1`` waves in flight (depth 1 = sync
        right here, the synchronous finisher — the dispatch itself is
        still async w.r.t. the server window already queued on the
        device).  Once the admission queue is empty (``queue_drained``)
        few future retires remain to help a bucket coalesce, so the wave
        threshold halves — stranded sub-wave classes ship while server
        windows still run instead of falling to the tail."""
        if not self._ready and not self._pending:
            return
        t0 = time.perf_counter()
        with self._eng.obs.tracer.span("finish_flush", tick=now):
            while self._pending and self._oldest_ready():
                self._sync_oldest(now)
            floor = self._wave_lanes // 2 if queue_drained else self._wave_lanes
            for key in [k for k, n in self._staged.items() if n >= floor]:
                self._dispatch(self._take_wave(key), now)
                while len(self._pending) >= self._depth:
                    self._sync_oldest(now)
        self.host_s += time.perf_counter() - t0

    def drain(self, now: int) -> None:
        """Close the tail after the server loop: whatever is still staged
        or in flight syncs here — the only stretch of the stream finisher
        that does NOT overlap server windows.  Each leftover class bucket
        ships as its own wave(s), shortest segment first, so every tail
        program's fori bound is its lanes' exact ``K - cut``; every wave
        is dispatched before the first sync, keeping the device queue
        full."""
        if not self._ready and not self._pending:
            return
        t0 = time.perf_counter()
        with self._eng.obs.tracer.span("finish_drain", tick=now):
            for key in sorted(self._ready, key=lambda k: k[2] - k[1]):
                while key in self._ready:
                    self._dispatch(self._take_wave(key), now)
            while self._pending:
                self._sync_oldest(now)
        dt = time.perf_counter() - t0
        self.host_s += dt
        self.tail_s += dt

    def summary(self) -> Dict:
        return finish_summary("stream", self.host_s, self.tail_s,
                              batches=self.batches, programs=self.programs,
                              lanes=self.lanes)


class ServeEngine:
    """Fixed-capacity slot array + k-tick fused scan window + async
    retire/refill.  Construct with ``ServeEngine(EngineConfig(...),
    server_params)`` and call :meth:`serve` — the single entrypoint.

    ``config.apply_fn(params, x, t) -> eps_hat`` is the backbone
    convention shared with :class:`repro.core.trainer.CollaFuseTrainer`;
    ``server_params`` is the shared server model.  See
    :class:`EngineConfig` for every knob (sampler menu, KID admission,
    StepBackend, mesh, scan/async depths, pod-mode lane ownership) —
    all are resolved/validated ONCE here, at construction.

    The legacy keyword constructor ``ServeEngine(sched, apply_fn,
    server_params, image_shape, **knobs)`` is kept for ONE release as a
    deprecation shim that builds the config for you — new call sites must
    pass an :class:`EngineConfig` (enforced by
    ``tools/check_engine_config.py`` in CI).
    """

    def __init__(self, config, server_params=None, *legacy, **kw):
        if isinstance(config, EngineConfig):
            if legacy or kw:
                raise TypeError(
                    "ServeEngine(EngineConfig, server_params) takes no "
                    f"further arguments (got {legacy!r}, {kw!r})")
            cfg = config
        else:
            # legacy positional signature:
            #   ServeEngine(sched, apply_fn, server_params, image_shape, **kw)
            warnings.warn(
                "ServeEngine(sched, apply_fn, server_params, image_shape, "
                "**knobs) is deprecated; build an EngineConfig and call "
                "ServeEngine(config, server_params)",
                DeprecationWarning, stacklevel=2)
            if len(legacy) != 2:
                raise TypeError(
                    "legacy signature is ServeEngine(sched, apply_fn, "
                    "server_params, image_shape, **knobs)")
            sched, apply_fn = config, server_params
            server_params, image_shape = legacy
            cfg = EngineConfig(sched=sched, apply_fn=apply_fn,
                               image_shape=image_shape, **kw)
        self.config = cfg
        self.sched = cfg.sched
        self.apply_fn = cfg.apply_fn
        self.server_params = server_params
        self.image_shape = cfg.image_shape
        self.slots = cfg.slots
        self.scheduler = cfg.scheduler if cfg.scheduler is not None \
            else FIFOScheduler()
        self.clip = cfg.clip
        self.backend = get_backend(cfg.step_backend)
        self.ticks_per_dispatch = cfg.ticks_per_dispatch
        self.async_depth = cfg.async_depth
        self.finish_mode = cfg.finish_mode
        self.finish_async_depth = cfg.finish_async_depth
        self.num_classes = cfg.num_classes
        self._conditional = cfg.num_classes > 0
        self.samplers = dict(cfg.samplers) if cfg.samplers is not None \
            else default_samplers(self.sched.T)
        for name, s in self.samplers.items():
            assert s.trajectory.T == self.sched.T, \
                f"sampler {name!r} built for T={s.trajectory.T}, " \
                f"engine schedule has T={self.sched.T}"
            assert not s.guided or self._conditional, \
                f"sampler {name!r} is guided but the engine is " \
                "unconditional (EngineConfig.num_classes == 0)"
        if getattr(self.scheduler, "samplers", None) is None:
            if hasattr(self.scheduler, "samplers"):
                # the lane-costing (and SJF pricing) menu: scheduler and
                # engine must agree on which samplers are guided or the
                # budget walk over- or under-commits the slot pool
                self.scheduler.samplers = self.samplers
        else:
            # a scheduler scoring a DIFFERENT menu would silently fall
            # back to the dense (1-c)·T cost for names it doesn't know
            # and misorder SJF — fail here, at construction
            assert_same_menu(self.scheduler.samplers, self.samplers,
                             "scheduler", "engine")
        # ---- KID-gated admission (repro.serve.admission) ----------------
        # engine and scheduler must share ONE policy: the scheduler gates
        # at select, the engine derives slot `end` counters / FLOPs from
        # the same cached decisions
        admission = cfg.admission
        if admission is None:
            admission = getattr(self.scheduler, "admission", None)
        self.admission = admission
        if admission is not None:
            assert admission.sched.T == self.sched.T, \
                f"admission policy calibrated for T={admission.sched.T}, " \
                f"engine schedule has T={self.sched.T}"
            if self._conditional:
                # the unconditional (x, t) view bakes the null label in;
                # the (x, t, y) view scores guided trajectories on the
                # conditional branch the serving path actually runs
                nc = self.num_classes

                def _uncond_fn(x, t, _p=server_params):
                    yn = jnp.full(x.shape[:1], nc, jnp.int32)
                    return self.apply_fn(_p, x, t, yn)

                admission.bind(
                    server_fn=_uncond_fn, samplers=self.samplers,
                    cond_server_fn=functools.partial(self.apply_fn,
                                                     server_params))
            else:
                admission.bind(
                    server_fn=functools.partial(self.apply_fn,
                                                server_params),
                    samplers=self.samplers)
            if self.scheduler.admission is None:
                self.scheduler.admission = admission
            assert self.scheduler.admission is admission, \
                "engine and scheduler must share one AdmissionPolicy"
        # ---- pod-mode lane ownership ------------------------------------
        from repro.parallel import sharding as shd
        self.hosts = cfg.hosts
        if cfg.hosts > 1:
            self.host_id = cfg.host_id if cfg.host_id is not None \
                else jax.process_index()
        else:
            # explicit None check: `cfg.host_id or 0` would conflate an
            # EXPLICIT host_id=0 with "unset" (both falsy) — equivalent
            # today only because validation pins host_id < hosts
            self.host_id = cfg.host_id if cfg.host_id is not None else 0
        self._lane_owned = \
            shd.lane_owners(self.slots, self.hosts) == self.host_id
        # ---- observability (repro.obs) ----------------------------------
        # resolved ONCE: NULL_OBS (falsy; every pillar a cached no-op) when
        # cfg.obs is None, so the obs-off hot path is bitwise the pre-obs
        # engine (gated in benchmarks.run --only obs_overhead)
        self.obs = resolve_obs(cfg.obs, host_id=self.host_id)
        if self.admission is not None:
            self.admission.tracer = self.obs.tracer
        self.scheduler.registry = self.obs.registry if self.obs else None
        # hoisted out of the tick: every registered trajectory's (4, K)
        # coefficient table concatenated column-wise (gathered per-lane in
        # SMEM by the fused kernel), plus the per-trajectory column offset
        # and padded timestep rows the tick gathers model-t from.  The
        # three live in ONE menu-state pytree (self._menu) threaded
        # through every jitted program as a TRACED argument — never a
        # closure constant — so register_sampler can swap in new arrays
        # (same shapes: spare columns/rows are preallocated here) without
        # a single retrace.
        self._traj_ids = {n: i for i, n in enumerate(self.samplers)}
        menu = list(self.samplers.values())
        lens = [s.K for s in menu]
        kmax = max(lens)
        self._kmax = kmax
        self.spare_columns = cfg.spare_columns
        self._static_names = frozenset(self.samplers)
        self._static_cols = sum(lens)
        # a dynamic trajectory occupies >= 1 column, so spare_columns
        # bounds the number of dynamic menu rows too
        n_rows = len(menu) + cfg.spare_columns
        tables = np.zeros((N_TABLE_ROWS,
                           self._static_cols + cfg.spare_columns),
                          np.float32)
        tables[:, :self._static_cols] = np.concatenate(
            [np.asarray(s.tables(self.sched)) for s in menu], axis=1)
        # unwritten spare columns are the identity step (c_eps=0, ar=1,
        # sigma=0, keep=0) at guidance w=0 (row GUIDANCE_ROW stays the
        # zero fill): a clamped junk gather from a retired/empty lane
        # passes x through instead of dividing by sqrt(0)
        tables[1, self._static_cols:] = 1.0
        offsets = np.zeros(n_rows, np.int32)
        offsets[:len(menu)] = np.cumsum([0] + lens[:-1])
        ts_pad = np.ones((n_rows, kmax), np.int32)
        for i, s in enumerate(menu):
            ts_pad[i, :s.K] = list(s.trajectory.timesteps)
        self._menu = {"tables": jnp.asarray(tables),
                      "offsets": jnp.asarray(offsets),
                      "ts_pad": jnp.asarray(ts_pad)}
        # dynamic-menu bookkeeping (register_sampler): free column
        # extents, free menu rows, and per-entry LRU stamps
        self._dyn: Dict[str, Dict] = {}
        self._dyn_rows = list(range(len(menu), n_rows))
        self._dyn_free = [(self._static_cols, cfg.spare_columns)] \
            if cfg.spare_columns else []
        self._use_clock = itertools.count(1)
        self._serving = False
        # guided_masked_index_step handles BOTH lane kinds in one fused
        # program: solo lanes (pair == own index) take the raw model eps
        # verbatim, paired lanes combine ε̂_u + w·(ε̂_c − ε̂_u) before the
        # shared masked step — so mixed guided/unguided traffic never
        # forks the scan program
        self._masked_index = functools.partial(
            self.backend.guided_masked_index_step, clip=self.clip)
        # the ONE lane tick both the k-scan window and the client finisher
        # run — see repro.diffusion.backend.make_lane_tick for the
        # done-latching contract the scan boundary relies on
        self._lane_tick = make_lane_tick(
            self.apply_fn, self._masked_index, kmax, self.image_shape,
            conditional=self._conditional)
        self._window_tick = self._lane_tick
        # per-request key derivation, jitted per batch size: the eager
        # vmapped fold_in/split trace costs ~5ms per ADMISSION, which at
        # pod scale (hundreds of in-flight requests) would dwarf the
        # denoise compute itself
        self._lane_keys = jax.jit(collafuse.lane_keys,
                                  static_argnums=(1,))
        self.mesh = cfg.mesh
        n_params = sum(x.size for x in jax.tree.leaves(server_params))
        # forward-only proxy (inference): ~2 FLOP per param per call
        self.flops_per_call = (cfg.flops_per_call
                               if cfg.flops_per_call is not None
                               else 2.0 * n_params)
        self._slot_shardings = None
        self._done_sharding = None
        if cfg.mesh is not None:
            from repro.models.layers import ShardCtx
            ctx = ShardCtx(mesh=cfg.mesh,
                           batch_axes=tuple(a for a in cfg.mesh.axis_names
                                            if a in ("pod", "data")))
            self._slot_shardings = shd.to_shardings(
                shd.slot_specs(jax.eval_shape(self._init_state), ctx),
                cfg.mesh)
            self._done_sharding = shd.gathered_sharding(cfg.mesh)
            # the window's step runs per device on its block of lanes: XLA
            # cannot partition the fused kernel (the finisher's lanes are
            # not sharded and keep the plain tick)
            sharded = LaneSharded(self.backend, cfg.mesh,
                                  self._slot_shardings["x"].spec[0])
            self._window_tick = make_lane_tick(
                self.apply_fn,
                functools.partial(sharded.guided_masked_index_step,
                                  clip=self.clip),
                kmax, self.image_shape, conditional=self._conditional)
        # async_depth > 1 holds window N's x/done refs while window N+1
        # computes, so the slot state cannot be donated to the dispatch;
        # the synchronous depth keeps the old zero-copy behaviour
        donate = (0,) if self.async_depth == 1 else ()
        self._tick = jax.jit(self._make_tick(), donate_argnums=donate)
        self._finish = jax.jit(self._make_finish())
        self._admit_prog = jax.jit(self._make_admit())
        self._stack_cache: Dict[tuple, tuple] = {}  # see _gather_stack

    # ------------------------------------------------------------------
    # device state
    # ------------------------------------------------------------------
    def _init_state(self):
        s = self.slots
        state = {
            "x": jnp.zeros((s,) + self.image_shape, jnp.float32),
            "pos": jnp.zeros((s,), jnp.int32),      # trajectory position
            "end": jnp.zeros((s,), jnp.int32),      # cut index (retire at)
            "traj": jnp.zeros((s,), jnp.int32),     # sampler-menu id
            "key": jnp.zeros((s, 2), jnp.uint32),
            "active": jnp.zeros((s,), bool),
            # conditional-serving lane state: class label (null for
            # unguided/shadow lanes), guided-pair partner index (own index
            # = solo, the init value — MUST be self-pairs so idle lanes
            # take the raw-eps path of guided_masked_index_step), and the
            # primary-lane flag (False only on a pair's uncond shadow)
            "y": jnp.full((s,), self.num_classes, jnp.int32),
            "pair": jnp.arange(s, dtype=jnp.int32),
            "cond": jnp.ones((s,), bool),
        }
        if self._slot_shardings is not None:
            state = jax.device_put(state, self._slot_shardings)
        return state

    def _make_tick(self):
        """The k-tick fused window: ``ticks_per_dispatch`` masked lane
        ticks under ONE ``lax.scan``.  Lanes reaching their cut latch
        (active drops, the carry holds bitwise — the shared lane tick's
        passthrough), so the boundary state carries every mid-window cut
        tensor exactly.  Returns the boundary state plus the (k, slots)
        per-tick done stack; under a mesh the stack is constrained
        REPLICATED so every pod host reads it with a local np.asarray."""
        k = self.ticks_per_dispatch

        def window(state, params, menu):
            def body(st, _):
                x, pos, key, done = self._window_tick(
                    params, menu, st["x"], st["pos"], st["key"], st["end"],
                    st["traj"], st["active"], st["y"], st["pair"],
                    st["cond"])
                new = {"x": x, "pos": pos, "end": st["end"],
                       "traj": st["traj"], "key": key,
                       "active": st["active"] & ~done,
                       "y": st["y"], "pair": st["pair"],
                       "cond": st["cond"]}
                if self._slot_shardings is not None:
                    new = jax.lax.with_sharding_constraint(
                        new, self._slot_shardings)
                return new, done
            state, done_seq = jax.lax.scan(body, state, None, length=k)
            if self._done_sharding is not None:
                done_seq = jax.lax.with_sharding_constraint(
                    done_seq, self._done_sharding)
            return state, done_seq
        return window

    def _make_finish(self):
        def finish(client_stack, menu, x, pos, end, traj, keys, valid):
            # leading axis = client, second = (padded) lanes of that
            # client.  The engine passes ONE client's row (_pack_finish),
            # so the vmap lowers to plain convolutions over that client's
            # lanes; over n rows it would lower every convolution to a
            # grouped one (feature_group_count = n).
            n_steps = jnp.max(jnp.where(valid, end - pos, 0))
            # the client segment is ALWAYS unguided — every finisher lane
            # is its own pair (solo ⇒ raw eps even on a guided sampler's
            # columns) conditioned on the null label; this is what keeps
            # the private client finish bitwise the pre-guidance path
            width = x.shape[1]
            y_null = jnp.full((width,), self.num_classes, jnp.int32)
            pair_solo = jnp.arange(width, dtype=jnp.int32)
            cond_prim = jnp.ones((width,), bool)

            def per_client(params, xg, pg, eg, tg, kg, vg):
                def body(_, carry):
                    xc, p, key = carry
                    xc, p, key, _ = self._lane_tick(
                        params, menu, xc, p, key, eg, tg, vg, y_null,
                        pair_solo, cond_prim)
                    return (xc, p, key)
                # traced bound -> one while-program shared by every cut mix
                xo, _, _ = jax.lax.fori_loop(0, n_steps, body, (xg, pg, kg))
                return xo
            return jax.vmap(per_client)(client_stack, x, pos, end, traj,
                                        keys, valid)
        return finish

    # ------------------------------------------------------------------
    # dynamic sampler menus (EngineConfig.spare_columns)
    # ------------------------------------------------------------------
    def register_sampler(self, name: str, sampler: Sampler) -> int:
        """Register an AD-HOC trajectory into the live engine — no
        retrace.  The sampler's (5, K) coefficient block (step rows plus
        its guidance-scale row, so guided trajectories register the same
        way) lands in
        preallocated spare columns with ONE device scatter, its padded
        timestep row and column offset fill a spare menu row, and every
        jitted program (`_tick`, `_finish`, `_admit`) keeps its cache:
        the menu is a traced argument whose shapes were fixed at
        construction (zero new compiles is gated in ``benchmarks.run
        --only hetero_packing``).

        When the spare region is full, LRU UNREFERENCED dynamic entries
        are evicted (freed extents merge with their neighbours, so the
        region cannot fragment permanently); static menu entries are
        never evicted.  The scheduler's SJF cost menu and the admission
        policy's score/decision caches are updated in the same call, so
        pricing and gating key on the new entry immediately.  Call
        between :meth:`serve` calls (every call boundary is a window
        boundary: no scan windows are in flight and the queue is
        drained, so every dynamic entry is unreferenced).  Returns the
        assigned trajectory id."""
        assert not self._serving, \
            "register_sampler must run at a window boundary — between " \
            "serve() calls, not from inside one"
        assert self.spare_columns > 0, \
            "EngineConfig.spare_columns == 0: no spare table columns " \
            "were preallocated for dynamic sampler registration"
        assert name not in self._static_names, \
            f"sampler {name!r} is a static menu entry — static " \
            "trajectories are immutable for the engine's lifetime"
        assert sampler.trajectory.T == self.sched.T, \
            f"sampler {name!r} built for T={sampler.trajectory.T}, " \
            f"engine schedule has T={self.sched.T}"
        assert not sampler.guided or self._conditional, \
            f"sampler {name!r} is guided (w={sampler.w:g}) but the " \
            "engine is unconditional (EngineConfig.num_classes == 0)"
        assert sampler.K <= self._kmax, \
            f"dynamic sampler {name!r} has K={sampler.K} > kmax=" \
            f"{self._kmax} — the padded timestep rows are preallocated " \
            "at the static menu's longest trajectory"
        if name in self._dyn:
            self._evict(name)          # re-registration replaces in full
        col = self._alloc_extent(sampler.K)
        tid = self._dyn_rows.pop(0)
        # ONE scatter writes the whole (4, K) coefficient block; the two
        # int row updates are O(kmax) metadata riding the same boundary
        tables = self._menu["tables"].at[
            :, col:col + sampler.K].set(sampler.tables(self.sched))
        offsets = self._menu["offsets"].at[tid].set(col)
        row = jnp.asarray(list(sampler.trajectory.timesteps)
                          + [1] * (self._kmax - sampler.K), jnp.int32)
        ts_pad = self._menu["ts_pad"].at[tid].set(row)
        self._menu = {"tables": tables, "offsets": offsets,
                      "ts_pad": ts_pad}
        self._dyn[name] = {"tid": tid, "col": col, "K": sampler.K,
                           "stamp": next(self._use_clock)}
        self.samplers[name] = sampler
        self._traj_ids[name] = tid
        sched_menu = getattr(self.scheduler, "samplers", None)
        if sched_menu is not None and sched_menu is not self.samplers:
            sched_menu[name] = sampler
        if self.admission is not None:
            self.admission.register_sampler(name, sampler)
        return tid

    def registered_samplers(self) -> Dict[str, int]:
        """Live DYNAMIC menu entries: name -> trajectory id."""
        return {n: e["tid"] for n, e in self._dyn.items()}

    def _alloc_extent(self, K: int) -> int:
        """First-fit a K-column extent in the spare region, evicting LRU
        dynamic entries until one exists."""
        assert K <= self.spare_columns, \
            f"dynamic trajectory needs {K} columns; only " \
            f"{self.spare_columns} spare columns were preallocated"
        while True:
            for i, (start, length) in enumerate(self._dyn_free):
                if length >= K:
                    if length == K:
                        del self._dyn_free[i]
                    else:
                        self._dyn_free[i] = (start + K, length - K)
                    return start
            assert self._dyn, "spare-extent accounting lost columns"
            lru = min(self._dyn, key=lambda n: self._dyn[n]["stamp"])
            self._evict(lru)

    def _evict(self, name: str) -> None:
        """Drop one dynamic menu entry: return its extent (merged with
        adjacent free extents) and its menu row, and scrub the name from
        the shared sampler menu and the admission caches.  The stale
        device coefficients need no write — no trajectory id points at
        them until the extent is reallocated."""
        e = self._dyn.pop(name)
        self._dyn_rows.append(e["tid"])
        self._dyn_free.append((e["col"], e["K"]))
        self._dyn_free.sort()
        merged = []
        for start, length in self._dyn_free:
            if merged and merged[-1][0] + merged[-1][1] == start:
                merged[-1] = (merged[-1][0], merged[-1][1] + length)
            else:
                merged.append((start, length))
        self._dyn_free = merged
        del self.samplers[name]
        del self._traj_ids[name]
        sched_menu = getattr(self.scheduler, "samplers", None)
        if sched_menu is not None and sched_menu is not self.samplers:
            sched_menu.pop(name, None)
        if self.admission is not None:
            self.admission.unregister_sampler(name)

    # ------------------------------------------------------------------
    # host-side admission / retirement
    # ------------------------------------------------------------------
    # -- sampler plumbing ----------------------------------------------
    def _sampler_of(self, req: Request) -> Sampler:
        assert req.sampler in self.samplers, \
            f"request {req.req_id} names sampler {req.sampler!r}; engine " \
            f"menu: {sorted(self.samplers)}"
        return self.samplers[req.sampler]

    def _decision(self, req: Request) -> Optional[AdmissionDecision]:
        """The (cached) admission decision for a request; None ungated."""
        return self.admission.decide(req) if self.admission is not None \
            else None

    def _effective_cut(self, req: Request) -> int:
        """Trajectory position the request's lanes retire at (= server
        model calls it costs).  Under a KID gate this is the admission
        decision's EFFECTIVE cut — nominal for plain admits, noisier
        (smaller) for bumped requests; ungated it is the nominal CutPlan
        cut, bitwise the pre-gate behaviour."""
        d = self._decision(req)
        if d is not None:
            assert d.served, \
                f"request {req.req_id} was rejected at admission " \
                f"({d.describe()}) — it has no serving cut"
            return d.effective_cut
        return CutPlan(self.sched.T, req.cut_ratio).cut_index(
            self._sampler_of(req))

    def _steps_of(self, req: Request):
        """(server, client) model-call split on the request's trajectory —
        the metrics' FLOP accounting.  Bumped requests shift steps from
        the server to the client (the cut moved noisier)."""
        cut = self._effective_cut(req)
        return cut, self._sampler_of(req).K - cut

    def _lanes_of(self, req: Request) -> int:
        """Slot-pool lanes the request occupies: ``batch`` images, ×2 when
        its sampler is guided (one cond+uncond lane pair per image) — the
        same costing the scheduler's budget walk uses."""
        return req.batch * (2 if self._sampler_of(req).guided else 1)

    def _admit_host(self, req: Request, lanes: List[int], now: int,
                    inflight: Dict, lane_req: np.ndarray,
                    lane_img: np.ndarray, lane_shadow: np.ndarray,
                    metrics: ServeMetrics):
        """Host-side bookkeeping for one admitted request; returns its
        per-LANE (k_init, k_srv, y, pair, cond) rows for the boundary's
        batched slot write.

        GUIDED requests take ``2·batch`` lanes: ``lanes[:batch]`` are the
        PRIMARY (cond, real-label) lanes carrying the request's normal
        per-image key chain, ``lanes[batch:]`` their uncond SHADOWS —
        same x_T draw (same k_init row), null label, mutual ``pair``
        pointers.  Both members of a pair step to bit-identical x (the
        shadow borrows the primary's noise inside the guided step), so
        at w=0 the primary chain is bitwise the unguided one.  Shadows
        are marked in ``lane_shadow`` so retirement never emits their
        rows — a pair is ONE image of ONE request."""
        smp = self._sampler_of(req)
        b = req.batch
        k_init, k_srv, k_cli = self._lane_keys(req.key, b)
        k_init, k_srv = np.asarray(k_init), np.asarray(k_srv)
        lane_req[lanes] = req.req_id
        if smp.guided:
            assert len(lanes) == 2 * b, (len(lanes), b)
            lane_img[lanes] = np.concatenate([np.arange(b), np.arange(b)])
            lane_shadow[lanes[b:]] = True
            k_init = np.concatenate([k_init, k_init])   # shadow: same x_T
            k_srv = np.concatenate([k_srv, k_srv])
            ys = np.concatenate([np.full(b, req.label, np.int32),
                                 np.full(b, self.num_classes, np.int32)])
            pairs = np.concatenate([lanes[b:], lanes[:b]]).astype(np.int32)
            conds = np.concatenate([np.ones(b, bool), np.zeros(b, bool)])
        else:
            assert len(lanes) == b, (len(lanes), b)
            lane_img[lanes] = np.arange(b)
            ys = np.full(b, self.num_classes, np.int32)
            pairs = np.asarray(lanes, np.int32)         # solo: own index
            conds = np.ones(b, bool)
        inflight[req.req_id] = {
            "request": req, "remaining": len(lanes), "admit_tick": now,
            "k_cli": np.asarray(k_cli),
            "x_mid": np.zeros((b,) + self.image_shape, np.float32),
            "owned": np.zeros((b,), bool),
            "exact_tick": -1,            # max exact finish over its lanes
            # trajectory class for the per-window occupancy mix: lanes
            # sharing it retire at the same boundary when co-admitted;
            # the guidance scale keys the class — guided pairs occupy two
            # lane-ticks per image and must not pool with unguided lanes
            "cls": f"{req.sampler}@{self._effective_cut(req)}@{smp.w:g}",
        }
        metrics.on_admit(req.req_id, now)
        if self.obs:
            self.obs.request(req.req_id, "admitted", tick=now,
                             lanes=[int(x) for x in lanes])
        return k_init, k_srv, ys, pairs, conds

    def _make_admit(self):
        """The fused boundary-refill program: x_T draw + all 9 slot
        writes in ONE jit.  Pad rows carry ``idx == slots`` — out of
        bounds, so their scatter writes DROP (``mode="drop"``); real
        rows are bitwise identical to the old eager update chain (the
        vmapped per-lane draw is elementwise over the key rows, so
        neighbours — padding included — never change a lane's x_T).  A
        guided pair's shadow lane carries its primary's k_init row, so
        both draw the SAME x_T."""
        def admit(state, idx, k_init, k_srv, ends, trajs, ys, pairs,
                  conds):
            x_T = jax.vmap(
                lambda k: jax.random.normal(k, self.image_shape,
                                            jnp.float32))(k_init)
            return {
                "x": state["x"].at[idx].set(x_T, mode="drop"),
                "pos": state["pos"].at[idx].set(0, mode="drop"),
                "end": state["end"].at[idx].set(ends, mode="drop"),
                "traj": state["traj"].at[idx].set(trajs, mode="drop"),
                "key": state["key"].at[idx].set(k_srv, mode="drop"),
                "active": state["active"].at[idx].set(True, mode="drop"),
                "y": state["y"].at[idx].set(ys, mode="drop"),
                "pair": state["pair"].at[idx].set(pairs, mode="drop"),
                "cond": state["cond"].at[idx].set(conds, mode="drop"),
            }
        return admit

    def _admit_device(self, state, admits):
        """ONE batched, jitted slot-array refill for every request
        admitted at this window boundary: one program per BOUNDARY
        instead of an eager update chain per request (at pod scale —
        hundreds of in-flight requests — the per-request eager updates
        dominate wall time, not the denoise compute).  The lane count is
        padded to the next power of two so the program compiles
        O(log slots) times, never per admit-batch shape."""
        n = sum(len(ln) for _, ln, *_ in admits)
        m = 1 << (n - 1).bit_length()
        lanes = np.full(m, self.slots, np.int32)   # pads point off-array
        k_init = np.zeros((m, 2), np.uint32)
        k_srv = np.zeros((m, 2), np.uint32)
        ends = np.zeros(m, np.int32)
        trajs = np.zeros(m, np.int32)
        ys = np.zeros(m, np.int32)
        pairs = np.zeros(m, np.int32)              # pad rows drop anyway
        conds = np.ones(m, bool)
        off = 0
        for req, ln, ki, ks, yr, pr, cr in admits:
            sl = slice(off, off + len(ln))
            lanes[sl] = ln
            k_init[sl] = ki
            k_srv[sl] = ks
            ends[sl] = self._effective_cut(req)
            trajs[sl] = self._traj_ids[req.sampler]
            ys[sl] = yr
            pairs[sl] = pr
            conds[sl] = cr
            off += len(ln)
        return self._admit_prog(state, lanes, k_init, k_srv, ends, trajs,
                                ys, pairs, conds)

    def _host_rows(self, arr, lanes: List[int]) -> Dict[int, np.ndarray]:
        """Materialize ``arr[lane]`` for the lanes THIS host owns.

        Off-pod (or simulated hosts in one process) the array is fully
        addressable and one gather serves all owned lanes.  Under a real
        multi-process ``jax.distributed`` run the slot axis is sharded
        across processes, so each host walks its ADDRESSABLE shards and
        copies only the owned rows they cover — zero cross-host traffic
        for the (k·slots·image)-sized tensors (only the bool done stack is
        gathered)."""
        owned = [ln for ln in lanes if self._lane_owned[ln]]
        if not owned:
            return {}
        if getattr(arr, "is_fully_addressable", True):
            vals = np.asarray(
                jnp.take(arr, jnp.asarray(owned, jnp.int32), axis=0))
            return {ln: vals[j] for j, ln in enumerate(owned)}
        out: Dict[int, np.ndarray] = {}
        for shard in arr.addressable_shards:
            sl = shard.index[0]
            start = sl.start or 0
            stop = sl.stop if sl.stop is not None else arr.shape[0]
            hit = [ln for ln in owned if start <= ln < stop]
            if hit:
                data = np.asarray(shard.data)
                for ln in hit:
                    out[ln] = data[ln - start]
        return out

    def _sync_window(self, win, inflight, lane_req, lane_img, lane_shadow,
                     completions, metrics) -> None:
        """Block on ONE in-flight window's done stack and run its retire
        bookkeeping.  ``retire_tick`` is the window BOUNDARY (start + k);
        the per-tick stack recovers each lane's exact finish for the
        boundary-lag metric (≤ k-1 by construction) and the EXACT
        per-tick occupancy samples (``ServeMetrics.on_window_exact`` —
        the stack is already being synced, no new device round-trip).
        A guided pair's SHADOW lane frees its slot here like any other
        lane but never emits a row: no x_mid write, no ownership, no
        boundary-lag sample — the pair is one image of one request."""
        done_seq, x_ref, start, n_active = win
        tracer = self.obs.tracer
        with tracer.span("sync_wait", start_tick=start):
            done_np = np.asarray(done_seq)       # (k, slots); blocks here
        k = done_np.shape[0]
        boundary = start + k
        metrics.on_window_exact(n_active, done_np.sum(axis=1))
        lanes = np.nonzero(done_np.any(axis=0))[0]
        if not lanes.size:
            return
        first = done_np.argmax(axis=0)           # first done tick per lane
        with tracer.span("retire", start_tick=start,
                         lanes=int(lanes.size)):
            with tracer.span("retire_rows"):
                rows = self._host_rows(
                    x_ref, [ln for ln in lanes.tolist() if not lane_shadow[ln]])
            for lane in lanes.tolist():
                rec = inflight[int(lane_req[lane])]
                if not lane_shadow[lane]:
                    metrics.on_boundary_lag(int(k - 1 - first[lane]))
                    img = int(lane_img[lane])
                    if lane in rows:
                        rec["x_mid"][img] = rows[lane]
                        rec["owned"][img] = True
                rec["remaining"] -= 1
                rec["exact_tick"] = max(rec["exact_tick"],
                                        start + int(first[lane]))
                if rec["remaining"] == 0:
                    r = rec["request"]
                    metrics.on_retire(r.req_id, boundary)
                    self.obs.request(r.req_id, "retired", tick=boundary,
                                     exact_tick=rec["exact_tick"])
                    completions[r.req_id] = Completion(
                        request=r, x_mid=rec["x_mid"],
                        admit_tick=rec["admit_tick"], retire_tick=boundary,
                        k_cli=rec["k_cli"], owned=rec["owned"])
                    # retired-request hook: the streaming client finisher
                    # (and any other subscriber) learns the request's last
                    # lane is done at this boundary
                    self.scheduler.notify_retired(r, boundary)
                lane_req[lane] = lane_img[lane] = -1
                lane_shadow[lane] = False

    def _serve_server(self, requests: List[Request],
                      max_ticks: Optional[int] = None,
                      client_stack=None) -> ServeResult:
        """Server segment of every request: admit from the queue, dispatch
        k-tick scan windows (up to ``async_depth`` in flight), retire at
        window boundaries until drained.  Without ``client_stack``,
        completions carry ``x_mid`` only and :meth:`serve` adds the client
        finish afterwards (``finish_mode="drain"``); WITH it (threaded
        down by ``serve`` in ``finish_mode="stream"``), a
        :class:`_FinishPipeline` runs the client segment inside this
        loop — freshly-retired requests are packed and dispatched at each
        boundary while later server windows are in flight, and the loop's
        single wall timer covers both segments (no double-counting).

        Under a KID gate every request gets an :class:`AdmissionDecision`
        (surfaced in ``ServeResult.decisions``): to-be-rejected requests
        still enter the queue and are formally dropped by the scheduler's
        select gate — they never occupy a slot and have no completion."""
        assert len({r.req_id for r in requests}) == len(requests), \
            "duplicate req_ids: completions/inflight are keyed by req_id"
        k = self.ticks_per_dispatch
        # LRU stamps for the dynamic menu: a serve that names an entry
        # makes it most-recently-used for register_sampler's eviction
        for r in requests:
            if r.sampler in self._dyn:
                self._dyn[r.sampler]["stamp"] = next(self._use_clock)
        obs = self.obs
        tracer = obs.tracer
        obs.timelines.reset()       # lifecycles are per serve() call
        # queueing, scoring and the fresh slot array: the host work
        # before the first admission
        with tracer.span("enqueue", requests=len(requests)):
            decisions: Dict[int, AdmissionDecision] = {}
            for r in requests:
                assert self._lanes_of(r) <= self.slots, \
                    f"request {r.req_id} needs {self._lanes_of(r)} lanes " \
                    f"(batch {r.batch}" + \
                    (", guided ×2" if self._sampler_of(r).guided else "") + \
                    f") > capacity {self.slots}"
                self._sampler_of(r)                    # fail fast on bad names
                obs.request(r.req_id, "queued", tick=r.arrival_tick,
                            batch=r.batch, cut_ratio=r.cut_ratio,
                            sampler=r.sampler)
                d = self._decision(r)                  # cached; gate once here
                if d is not None:
                    decisions[r.req_id] = d
                    obs.request(r.req_id, "scored", action=d.action,
                                kid=d.kid, effective_cut=d.effective_cut)
                    if not d.served:
                        obs.request(r.req_id, "rejected")

            def _served(r):
                return r.req_id not in decisions or decisions[r.req_id].served

            # zero-server-step requests (cut position 0, e.g. c=1 — or bumped
            # all the way to full concealment) complete at arrival (x_mid =
            # x_T) without ever occupying a slot
            local_only = collections.deque(sorted(
                (r for r in requests
                 if _served(r) and self._effective_cut(r) == 0),
                key=lambda r: r.arrival_tick))
            for r in requests:
                if not _served(r):
                    self.scheduler.add(r)   # dropped at the select gate below
                elif self._effective_cut(r) > 0:
                    self.scheduler.add(r)
            if max_ticks is None:
                span = max((r.arrival_tick for r in requests), default=0)
                total = sum(self._effective_cut(r) for r in requests
                            if _served(r))
                # liveness bound: serving work + per-request window overhead
                # (a lane can idle up to k·async_depth ticks between reaching
                # its cut and its boundary sync freeing the slot)
                overhead = k * (self.async_depth + 1)
                max_ticks = span + total + self._kmax + 16 + \
                    overhead * max(1, len(requests))

            state = self._init_state()
        lane_req = np.full(self.slots, -1, np.int64)
        lane_img = np.full(self.slots, -1, np.int64)
        lane_shadow = np.zeros(self.slots, bool)   # uncond halves of pairs
        inflight: Dict[int, Dict] = {}
        completions: Dict[int, Completion] = {}
        # in-flight scan windows, oldest first: (done_seq devicearray,
        # boundary-state x ref, start tick).  Retired lanes hold x bitwise
        # in every LATER window, but pairing each done stack with its own
        # boundary x means syncing window N never blocks on window N+1.
        pending: collections.deque = collections.deque()
        metrics = ServeMetrics(self.slots,
                               registry=obs.registry if obs else None)
        metrics.start()
        # obs plumbing resolved before the loop: JSONL snapshot cadence
        # and the live queue/inflight gauges
        metrics_path = obs.config.metrics_path if obs else None
        metrics_every = obs.config.metrics_every if obs else 1
        if obs:
            g_queue = obs.registry.gauge(
                "serve_queue_depth", "requests waiting in the scheduler")
            g_inflight = obs.registry.gauge(
                "serve_inflight_requests", "requests occupying slots")
        windows_synced = 0
        # ---- streaming client finisher (finish_mode="stream") -----------
        # constructed only when serve() threads the stack down here; the
        # scheduler's retired-request hook stages each completed request
        # and the boundary flushes below dispatch grouped finish batches
        # while later server windows are in flight
        finisher: Optional[_FinishPipeline] = None
        unsubscribe = None
        if client_stack is not None:
            finisher = _FinishPipeline(self, client_stack, metrics)
            unsubscribe = self.scheduler.on_retired(
                lambda req, tick: finisher.stage(completions[req.req_id]))
        self._serving = True
        t0 = time.perf_counter()
        now = 0

        def drain_local(now):
            # ONE batched x_T draw per boundary across every due
            # local-only request: the vmapped normal is elementwise over
            # the concatenated key rows, so each lane's slice is bitwise
            # the per-request draw it replaces
            due = []
            while local_only and local_only[0].arrival_tick <= now:
                due.append(local_only.popleft())
            if not due:
                return
            lane_keys = [self._lane_keys(r.key, r.batch) for r in due]
            x_T = np.asarray(jax.vmap(lambda k: jax.random.normal(
                k, self.image_shape, jnp.float32))(
                    jnp.concatenate([ki for ki, _, _ in lane_keys])))
            off = 0
            for r, (_, _, k_cli) in zip(due, lane_keys):
                metrics.on_admit(r.req_id, now)
                metrics.on_retire(r.req_id, now)
                if obs:
                    obs.request(r.req_id, "admitted", tick=now, local=True)
                    obs.request(r.req_id, "retired", tick=now,
                                exact_tick=now)
                completions[r.req_id] = Completion(
                    request=r, x_mid=x_T[off:off + r.batch],
                    admit_tick=now, retire_tick=now,
                    k_cli=np.asarray(k_cli),
                    owned=np.ones((r.batch,), bool))
                off += r.batch
                self.scheduler.notify_retired(r, now)

        def more_server_work() -> bool:
            # is there anything left for the server loop to overlap a
            # finish batch with — windows in flight, lanes still denoising,
            # or queued arrivals that will dispatch more windows?
            return bool(pending) or bool((lane_req >= 0).any()) \
                or len(self.scheduler) > 0 or bool(local_only)

        def sync_oldest():
            nonlocal windows_synced
            self._sync_window(pending.popleft(), inflight, lane_req,
                              lane_img, lane_shadow, completions, metrics)
            windows_synced += 1
            if metrics_path and windows_synced % metrics_every == 0:
                obs.registry.write_jsonl(metrics_path, host=self.host_id,
                                         window=windows_synced)

        try:
            while True:
                # one span per iteration: host work no child span covers
                # is this span's self time
                with tracer.span("window", tick=now):
                    # ---- admission: refill freed slots at the boundary ------
                    with tracer.span("admit", tick=now):
                        drain_local(now)
                        free = np.nonzero(lane_req < 0)[0].tolist()
                        admits = []
                        for req in self.scheduler.select_window(
                                len(free), now, k):
                            need = self._lanes_of(req)   # guided pair = 2/image
                            lanes, free = free[:need], free[need:]
                            row = self._admit_host(req, lanes, now, inflight,
                                                   lane_req, lane_img,
                                                   lane_shadow, metrics)
                            admits.append((req, lanes) + row)
                        if admits:
                            with tracer.span("admit_device", requests=len(admits)):
                                state = self._admit_device(state, admits)
                    n_active = int((lane_req >= 0).sum())
                    if obs:
                        g_queue.set(len(self.scheduler))
                        g_inflight.set(len(inflight))
                        tracer.counter("serve_occupancy", lanes=n_active,
                                       queued=len(self.scheduler))
                    if n_active == 0:
                        if pending:
                            # host thinks nothing is live but windows are in
                            # flight: their retires are what frees lanes
                            sync_oldest()
                            if finisher is not None and more_server_work():
                                finisher.flush(
                                    now,
                                    queue_drained=len(self.scheduler) == 0)
                            continue
                        if len(self.scheduler) == 0 and not local_only:
                            break
                        # idle: jump to the next arrival instead of spinning —
                        # recorded, not silent
                        nxt = [self.scheduler.next_arrival()]
                        if local_only:
                            nxt.append(local_only[0].arrival_tick)
                        target = max(now + 1,
                                     min(t for t in nxt if t is not None))
                        metrics.on_idle_gap(target - (now + 1))
                        if obs:
                            tracer.instant("idle_jump", from_tick=now,
                                           to_tick=target)
                        now = target
                        if now > max_ticks:
                            raise RuntimeError(
                                f"engine exceeded liveness bound ({max_ticks} "
                                f"ticks) with {len(self.scheduler)} queued / 0 "
                                "in-flight — scheduler starvation?")
                        continue
                    # ---- fragmentation + occupancy-by-class telemetry -------
                    # free lanes entering a window WHILE arrived demand waits
                    # are fragmentation: the scheduler could not shape the
                    # queue into them (ragged frees vs batch>1 heads).  The
                    # class mix is what wave packing homogenizes.
                    mix: Dict[str, int] = {}
                    for rec in inflight.values():
                        if rec["remaining"]:
                            mix[rec["cls"]] = mix.get(rec["cls"], 0) \
                                + rec["remaining"]
                    starved = any(r.arrival_tick <= now
                                  for r in self.scheduler._queue)
                    metrics.on_window_mix(mix, self.slots - n_active, starved,
                                          k)
                    # ---- ONE dispatch runs k fused ticks over every lane ----
                    with tracer.span("dispatch", tick=now, lanes=n_active):
                        state, done_seq = self._tick(state, self.server_params,
                                                     self._menu)
                    # exact per-tick occupancy is recovered from this window's
                    # done stack at sync time (on_window_exact), so the
                    # dispatch only records the window-start count + the refs
                    pending.append((done_seq, state["x"], now, n_active))
                    if obs and admits:
                        for req, *_ in admits:
                            obs.request(req.req_id, "first_tick", tick=now)
                    now += k
                    # ---- drain the pipeline down to async_depth - 1 ---------
                    # (async_depth=1: block right here — the synchronous loop)
                    while len(pending) >= self.async_depth:
                        sync_oldest()
                    if finisher is not None and more_server_work():
                        # boundary hand-off: requests whose last lane retired
                        # in the syncs above are packed and dispatched NOW,
                        # while server windows are in flight or about to be —
                        # this dispatch is the overlap the trace proves.  At
                        # the LAST boundary (no server work left) staged
                        # requests fall through to the post-loop drain
                        # instead, so overlap_frac only counts finish time
                        # that truly shared the loop with server compute
                        finisher.flush(now,
                                       queue_drained=len(self.scheduler) == 0)
                    if now > max_ticks:
                        raise RuntimeError(
                            f"engine exceeded liveness bound ({max_ticks} "
                            f"ticks) with {len(self.scheduler)} queued / "
                            f"{int((lane_req >= 0).sum())} in-flight — "
                            "scheduler starvation?")
        finally:
            self._serving = False
            # the hook closes over THIS call's completions dict — a stale
            # subscription would corrupt the scheduler's next serve()
            if unsubscribe is not None:
                unsubscribe()
        if finisher is not None:
            finisher.drain(now)
        wall = time.perf_counter() - t0
        # every to-be-rejected request must have been dropped by the
        # scheduler's select gate (the queue drained, so each was either
        # admitted or dropped) — cross-check the two gate sites agree
        dropped = {d.req_id for d in self.scheduler.take_rejections()}
        assert dropped == {rid for rid, d in decisions.items()
                           if not d.served}, \
            f"select-gate rejections {sorted(dropped)} disagree with " \
            f"admission decisions"
        summary = metrics.summary(wall, self.sched.T, self.flops_per_call,
                                  requests, steps_of=self._steps_of,
                                  decisions=decisions or None,
                                  guided_of=lambda r:
                                      self._sampler_of(r).guided)
        summary["ticks_per_dispatch"] = k
        summary["async_depth"] = self.async_depth
        summary["aging_promotions"] = getattr(self.scheduler,
                                              "aging_promotions", 0)
        if finisher is not None:
            # overlap-aware finish accounting: the loop's single wall
            # timer above already covers the streamed client segment, so
            # requests_per_s/images_per_s are NOT recomputed here — no
            # double-counting (finish_s is overlapped host time)
            summary.update(finisher.summary())
            summary["finish_async_depth"] = self.finish_async_depth
        timelines: Dict[int, List[Dict]] = {}
        if obs:
            if metrics_path:
                obs.registry.write_jsonl(metrics_path, host=self.host_id,
                                         window=windows_synced, final=True)
            path = obs.trace_path_for_host(self.hosts)
            if path:
                obs.tracer.export(path)
            timelines = obs.timelines.snapshot()
        return ServeResult(completions=completions, summary=summary,
                           wall_s=wall, decisions=decisions,
                           timelines=timelines)

    # ------------------------------------------------------------------
    # client finish: pack -> async dispatch -> scatter.  The SAME two
    # halves serve both finish modes — `_finish_clients` (the post-drain
    # reference path) is pack-everything + sync, the streaming finisher
    # (`_FinishPipeline`) packs each window boundary's freshly-retired
    # requests and defers the sync behind `finish_async_depth`.
    # ------------------------------------------------------------------
    def _pack_finish(self, comps: List[Completion], client_stack,
                     metrics: Optional[ServeMetrics] = None):
        """Split the lanes of ``comps`` by ``client_idx`` and dispatch ONE
        ``self._finish`` program per client present, over that client's
        cached one-row param stack: each program's model calls are plain
        (ungrouped) convolutions over the client's own lanes, padded to
        the pow-2 of that client's lane count, two at least (padding
        lanes ride the loop masked).  Each program's fori bound is the largest
        ``K - cut`` of its own lanes, so a wave of one step class runs
        every valid lane to exactly its own segment.  ``metrics`` (when
        given) counts the wave: its requests, lanes and programs, the
        client lane-steps dispatched (Σ over its programs of padded
        width × fori bound) and the useful ones (each valid lane's own
        ``K - cut``).

        Returns the wave's ``[(x0_ref, placement), ...]``, one pair per
        program, WITHOUT blocking on the device: ``x0_ref`` is the
        in-flight ``(1, width, *image)`` result and ``placement`` maps its
        rows back to completion rows as ``(comp, img, j)`` — hand each pair
        to :meth:`_scatter_finish`.  Per-lane outputs are independent of
        group composition: lanes past their cut latch bitwise (the shared
        lane tick's passthrough) and the fori bound is a masked max, so
        ANY partition of completions into pack calls yields
        bitwise-identical x0 rows."""
        assert comps
        n_clients = jax.tree.leaves(client_stack)[0].shape[0]
        wave, lane_steps, useful = [], 0, 0
        with self.obs.tracer.span("finish_pack"):
            by_client: Dict[int, List] = {}
            for comp in comps:
                r = comp.request
                assert 0 <= r.client_idx < n_clients, \
                    f"request {r.req_id} names client {r.client_idx}; stack " \
                    f"holds {n_clients}"
                cut = self._effective_cut(r)
                K = self._sampler_of(r).K
                tid = self._traj_ids[r.sampler]
                for i in range(r.batch):
                    by_client.setdefault(r.client_idx, []).append(
                        (comp, i, cut, K, tid))
            for ci in sorted(by_client):
                g = by_client[ci]
                # width is padded UP to the next power of two: an exact
                # width would hand ``self._finish`` a fresh (1, width)
                # shape almost every call — a jit recompile per request
                # batch.  Pow-2 buckets bound the cache at O(log slots)
                # entries; padding lanes ride the loop masked
                # (valid=False), so per-lane outputs are unchanged (cache
                # growth asserted in tests/test_admission.py).  Two lanes
                # at least: XLA:CPU lowers a one-row matmul to a
                # matrix-vector product that rounds differently, which
                # would tie a lane's bits to its program's width.
                width = max(2, 1 << (len(g) - 1).bit_length())
                shp = (1, width)
                x = np.zeros(shp + self.image_shape, np.float32)
                pos = np.zeros(shp, np.int32)
                end = np.zeros(shp, np.int32)
                traj = np.zeros(shp, np.int32)
                keys = np.zeros(shp + (2,), np.uint32)
                valid = np.zeros(shp, bool)
                placement = []
                for j, (comp, i, cut, K, tid) in enumerate(g):
                    x[0, j] = comp.x_mid[i]
                    pos[0, j], end[0, j], traj[0, j] = cut, K, tid
                    keys[0, j] = comp.k_cli[i]
                    valid[0, j] = True
                    placement.append((comp, i, j))
                steps = [K - cut for _, _, cut, K, _ in g]
                lane_steps += width * max(steps)
                useful += sum(steps)
                x0_ref = self._finish(self._gather_stack(client_stack, (ci,)),
                                      self._menu, x, pos, end, traj, keys,
                                      valid)
                wave.append((x0_ref, placement))
        if metrics is not None:
            metrics.on_finish_dispatch(
                len(comps), sum(len(p) for _, p in wave),
                lane_steps=lane_steps, useful_lane_steps=useful,
                programs=len(wave))
        return wave

    def _gather_stack(self, client_stack, present: tuple):
        """The client param stack compacted to the rows ``present`` (one
        client's row, for a finish program), cached — streamed waves hit
        the same rows every dispatch, and the eager gather is pure host
        overhead on the hot path.  The cache entry pins the source stack
        so an ``id()`` reuse after GC can never alias a stale gather."""
        hit = self._stack_cache.get((id(client_stack), present))
        if hit is not None and hit[0] is client_stack:
            return hit[1]
        idx = jnp.asarray(list(present))
        gathered = jax.tree.map(lambda a: a[idx], client_stack)
        self._stack_cache[(id(client_stack), present)] = (client_stack,
                                                          gathered)
        return gathered

    def _scatter_finish(self, x0_ref, placement) -> List[Completion]:
        """Block on one finish program and scatter its rows into the
        completions: fills ``Completion.x0``, flips ``client_finished``,
        and records the ``client_finished`` timeline stage ONCE per
        request (a request's lanes share its client, so one program
        carries them all).  Returns the completions it closed."""
        with self.obs.tracer.span("finish_wait"):
            x0 = np.asarray(x0_ref)              # blocks here
        finished: List[Completion] = []
        for comp, img, j in placement:
            if comp.x0 is None:
                comp.x0 = np.zeros((comp.request.batch,) + self.image_shape,
                                   np.float32)
                finished.append(comp)
            comp.x0[img] = x0[0, j]
        for comp in finished:
            comp.client_finished = True
            self.obs.request(comp.request.req_id, "client_finished")
        return finished

    def _finish_clients(self, result: ServeResult, client_stack) -> int:
        """Post-drain client finish — the REFERENCE implementation the
        streamed path is gated bitwise against (``benchmarks.run --only
        finisher_overlap``): every completion packed into ONE wave (one
        program per client) after the server queue drained.  Fills
        ``Completion.x0`` in place and flips ``client_finished``; returns
        the number of finish programs dispatched."""
        order = sorted(result.completions)
        if not order:
            return 0
        wave = self._pack_finish([result.completions[rid] for rid in order],
                                 client_stack)
        for x0_ref, placement in wave:
            self._scatter_finish(x0_ref, placement)
        return len(wave)

    def serve(self, requests: List[Request], client_stack=None,
              max_ticks: Optional[int] = None) -> ServeResult:
        """THE entrypoint: serve the server segment of ``requests`` and —
        when ``client_stack`` ([n_clients, ...] stacked private models) is
        supplied — finish every completion's client segment.

        Returns a :class:`ServeResult`: ``completions[req_id].x_mid`` is
        the disclosed tensor at the cut, ``.x0`` the finished images (None
        unless the client finish ran — check ``.client_finished``), and
        ``decisions`` the per-request admission record under a KID gate.
        ``max_ticks`` overrides the liveness bound (None derives it from
        the workload and the scan/async depths).

        ``config.finish_mode`` picks the client-segment path:
        ``"stream"`` (default) overlaps grouped finish batches with the
        server scan windows inside the host loop; ``"drain"`` runs the
        reference post-drain pass.  x0 is bitwise identical either way
        (``benchmarks.run --only finisher_overlap``); the summary's
        ``finish_s``/``overlap_frac`` report how much of the client
        segment overlapped server compute."""
        if client_stack is not None and self.finish_mode == "stream":
            return self._serve_server(requests, max_ticks=max_ticks,
                                      client_stack=client_stack)
        result = self._serve_server(requests, max_ticks=max_ticks)
        if client_stack is not None:
            t0 = time.perf_counter()
            with self.obs.tracer.span("finish_clients",
                                      requests=len(result.completions)):
                programs = self._finish_clients(result, client_stack)
            finish_s = time.perf_counter() - t0
            # drain mode: the finish ran AFTER the loop's wall timer
            # stopped, so it is added to the wall and throughput is
            # recomputed once from the combined clock (overlap_frac=0)
            result.wall_s += finish_s
            s = result.summary
            s.update(finish_summary(
                "drain", finish_s,
                batches=1 if result.completions else 0, programs=programs,
                lanes=sum(c.request.batch
                          for c in result.completions.values())))
            s["finish_async_depth"] = self.finish_async_depth
            s["requests_per_s"] = s["served"] / max(result.wall_s, 1e-9)
            s["images_per_s"] = s["images"] / max(result.wall_s, 1e-9)
            if self.obs:
                # refresh: the finish span + client_finished stages landed
                # after _serve_server's export/snapshot
                result.timelines = self.obs.timelines.snapshot()
                path = self.obs.trace_path_for_host(self.hosts)
                if path:
                    self.obs.tracer.export(path)
        return result

    # -- deprecated three-call surface (one release) --------------------
    def run(self, requests: List[Request],
            max_ticks: Optional[int] = None) -> ServeResult:
        """Deprecated: call :meth:`serve` (without a client stack) — the
        server segment is the same code path."""
        warnings.warn("ServeEngine.run() is deprecated; call serve()",
                      DeprecationWarning, stacklevel=2)
        return self._serve_server(requests, max_ticks=max_ticks)

    def finish_clients(self, result: ServeResult, client_stack) -> None:
        """Deprecated: pass ``client_stack`` to :meth:`serve` instead."""
        warnings.warn("ServeEngine.finish_clients() is deprecated; pass "
                      "client_stack to serve()",
                      DeprecationWarning, stacklevel=2)
        self._finish_clients(result, client_stack)


# ---------------------------------------------------------------------------
# sequential reference service (the benchmark baseline)
# ---------------------------------------------------------------------------
def _sequential_impl(sched: DiffusionSchedule, requests: List[Request],
                     server_fn: Callable, client_fn_for: Callable,
                     image_shape, samplers=None) -> Dict[int, Any]:
    outs = {}
    for r in sorted(requests, key=lambda r: (r.arrival_tick, r.req_id)):
        plan = CutPlan(sched.T, r.cut_ratio)
        smp = samplers[r.sampler] if samplers is not None else None
        x0, x_mid = collafuse.split_sample(
            sched, plan, server_fn, client_fn_for(r.client_idx), r.key,
            (r.batch,) + tuple(image_shape), return_intermediate=True,
            sampler=smp)
        outs[r.req_id] = (x0, x_mid)
    jax.block_until_ready([v[0] for v in outs.values()])
    return outs


def serve_sequential(config, requests: List[Request], *args,
                     samplers=None) -> Dict[int, Any]:
    """One ``split_sample`` call per request, in arrival order — the
    pre-engine serving path (O(requests) dispatch chains).  Used as the
    throughput baseline for the ≥3x continuous-batching gate.

    Preferred form — the SAME config the engine takes, so baselines and
    engine cannot drift apart in wiring::

        serve_sequential(EngineConfig(...), requests, server_params,
                         client_stack)

    Legacy form ``serve_sequential(sched, requests, server_fn,
    client_fn_for, image_shape, samplers=...)`` still works for callers
    holding bare functions."""
    if isinstance(config, EngineConfig):
        server_params, client_stack = args
        server_fn, client_fn_for = sequential_fns(
            config.apply_fn, server_params, client_stack)
        return _sequential_impl(config.sched, requests, server_fn,
                                client_fn_for, config.image_shape,
                                samplers=config.samplers)
    server_fn, client_fn_for, image_shape = args
    return _sequential_impl(config, requests, server_fn, client_fn_for,
                            image_shape, samplers=samplers)


def sequential_fns(apply_fn, server_params, client_stack):
    """(server_fn, client_fn_for) partials over a stacked client tree —
    the model plumbing both callers of :func:`serve_sequential` need."""
    from repro.optim import adamw
    server_fn = functools.partial(apply_fn, server_params)
    client_fn_for = lambda ci: functools.partial(
        apply_fn, adamw.tree_unstack(client_stack, ci))
    return server_fn, client_fn_for


def warmup_prefix(requests: List[Request]) -> List[Request]:
    """The minimal warmup workload for :func:`time_sequential`: ONE
    request per distinct compile key.  The sequential path's jit caches
    key on the lane shape (``batch``), the trajectory (``sampler``), and
    the segment split (``cut_ratio`` picks the loop bounds), so serving
    one representative of each distinct combination warms every cache the
    full workload would touch — without paying the full workload twice
    (2x wall at 256 requests, all of it baseline overhead)."""
    seen, prefix = set(), []
    for r in requests:
        key = (r.batch, r.sampler, r.cut_ratio)
        if key not in seen:
            seen.add(key)
            prefix.append(r)
    return prefix


def time_sequential(config, requests: List[Request], *args,
                    samplers=None) -> float:
    """Warmup pass + timed wall-clock of the sequential baseline.  Shared
    by ``launch/serve_diffusion.py --compare-sequential`` and the gated
    ``benchmarks.run --only serve_continuous`` so the baseline protocol
    cannot drift between the launcher and the benchmark.  Accepts the
    same two forms as :func:`serve_sequential`.  Warmup runs only
    :func:`warmup_prefix` — one request per distinct compile key — not
    the full workload twice."""
    serve_sequential(config, warmup_prefix(requests), *args,
                     samplers=samplers)
    t0 = time.perf_counter()
    serve_sequential(config, requests, *args, samplers=samplers)
    return time.perf_counter() - t0
