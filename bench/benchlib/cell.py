"""One cell's run: build the engine from the configuration, through its
family adapter (``bench/families/``) and the program's public
constructor, drive closed generation jobs through
``ServeEngine.serve`` with the streaming finisher, and record what the
metrics read.

The program is imported from ``<checkout>/src``; everything else here
belongs to the benchmark.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import spec
from benchlib import traffic as traffic_mod

# stage names of the program's per-request timelines
QUEUED, ADMITTED, RETIRED, FINISHED = ("queued", "admitted", "retired",
                                       "client_finished")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def base_key(seed: int):
    """Raw threefry key data of a seed of up to 64 bits (high, low)."""
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def weight_keys(seed: int, n_clients: int):
    """(server key, client keys) the weights are drawn from."""
    ks, kc = jax.random.split(jax.random.fold_in(base_key(seed), 1))
    return ks, jax.random.split(kc, n_clients)


class CompileCounter:
    """Counts compiles (a persistent-cache load is one too) and
    persistent-cache hits and writes, through ``jax.monitoring``."""

    def __init__(self):
        self.compiles = self.hits = self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self) -> Dict[str, int]:
        return {"compiles": self.compiles, "hits": self.hits,
                "writes": self.writes}


@dataclasses.dataclass
class JobRecord:
    """What one job left for the metrics and the check."""
    images: int
    ticks: int
    utilization_mean: float
    queue_wait_s: List[float]
    xc_s: List[float]
    x0_s: List[float]
    attempted: int
    failed: int
    outputs: Dict[int, dict]      # req id -> request, x_c, x_0 (host)


class Probe:
    """Counts the engine's scan-window dispatches and the shapes of its
    finish programs.  When ``traced`` it marks the host side of each call
    with a ``TraceAnnotation`` for the profile.  While ``warming``, every
    finish program runs with all its lanes marked invalid: it compiles or
    loads at its shape but steps no lane.  The finisher's arguments are
    found by name (``x``, ``valid``), so the program may add or reorder
    the others."""

    def __init__(self, eng, traced: bool = False):
        self.windows = 0
        self.finish_shapes = set()
        self.warming = False
        tick, finish = eng._tick, eng._finish
        finish_sig = inspect.signature(finish)
        ann = (jax.profiler.TraceAnnotation if traced
               else lambda name: contextlib.nullcontext())

        @functools.wraps(tick)
        def counted_tick(*args, **kwargs):
            self.windows += 1
            with ann("bench:dispatch_window"):
                return tick(*args, **kwargs)

        @functools.wraps(finish)
        def counted_finish(*args, **kwargs):
            bound = finish_sig.bind(*args, **kwargs)
            a = bound.arguments
            self.finish_shapes.add(tuple(a["x"].shape[:2]))
            if self.warming:
                a["valid"] = np.zeros_like(a["valid"])
            with ann("bench:dispatch_finish"):
                return finish(*bound.args, **bound.kwargs)

        eng._tick = counted_tick
        eng._finish = counted_finish


class Cell:
    """The engine, weights and traffic of one cell under one seed."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 traced: bool = False):
        from repro.diffusion.sampler import make_sampler
        from repro.diffusion.schedule import get_schedule
        from repro.obs import ObsConfig
        from repro.serve.engine import EngineConfig, ServeEngine
        from repro.serve.scheduler import make_scheduler

        self.config, self.traffic, self.seed = config, traffic, seed
        m = config["model"]
        self.model = m
        family = spec.family(config)
        self.num_classes = family.num_classes(m)
        e = config["engine"]
        self.slots, self.n_clients = e["slots"], e["clients"]
        self.image_shape = tuple(family.image_shape(m))
        T = config["schedule"]["T"]
        self.samplers = {
            name: make_sampler(T, s["family"], s.get("num_steps", 0),
                               s.get("eta", 1.0), guidance=s.get("guidance"))
            for name, s in traffic["samplers"].items()}
        pol = traffic["scheduler"]
        self.scheduler = make_scheduler(pol["policy"], T,
                                        samplers=self.samplers,
                                        pack=pol["pack"])
        init = family.init(m)

        @jax.jit
        def make_weights(k_server, k_clients):
            return init(k_server), jax.vmap(init)(k_clients)

        k_server, k_clients = weight_keys(seed, self.n_clients)
        self.server_params, self.client_stack = make_weights(k_server,
                                                             k_clients)
        self.engine = ServeEngine(EngineConfig(
            sched=get_schedule(config["schedule"]["name"], T),
            apply_fn=family.apply_fn(m), num_classes=self.num_classes,
            image_shape=self.image_shape, slots=self.slots,
            scheduler=self.scheduler, clip=e["clip"],
            samplers=self.samplers,
            ticks_per_dispatch=e["ticks_per_dispatch"],
            async_depth=e["async_depth"], finish_mode="stream",
            finish_async_depth=e["finish_async_depth"],
            obs=ObsConfig(trace=False, timelines=True)), self.server_params)
        self.probe = Probe(self.engine, traced=traced)
        self.specs = traffic_mod.composition(traffic, self.slots,
                                             self.n_clients,
                                             self.num_classes)
        self.images_per_job = traffic_mod.job_images(self.specs)
        self.lane_steps = traffic_mod.lane_steps(config, traffic, self.specs)
        self._job_keys = jax.jit(functools.partial(_job_keys,
                                                   n=len(self.specs)))
        self._base = base_key(seed)
        if traced:
            select = self.scheduler.select_window

            def annotated_select(free, now, k):
                with jax.profiler.TraceAnnotation("bench:admit"):
                    return select(free, now, k)
            self.scheduler.select_window = annotated_select

    def requests(self, job: int):
        from repro.serve.scheduler import Request
        keys = np.asarray(self._job_keys(self._base, job))
        return [Request(req_id=i, key=keys[i], batch=s.batch,
                        cut_ratio=s.cut_ratio, client_idx=s.client,
                        arrival_tick=0, sampler=s.sampler, label=s.label)
                for i, s in enumerate(self.specs)]

    def run_job(self, job: int, keep_outputs: bool = True) -> JobRecord:
        reqs = self.requests(job)
        res = self.engine.serve(reqs, self.client_stack)
        return record(res, reqs, keep_outputs)

    def warm_up(self) -> None:
        """Set-up's job: the window's composition, so every program and
        shape the window uses compiles or loads.  Its finish programs step
        no lane (their shapes are what set-up needs, not their work)."""
        self.probe.warming = True
        try:
            self.run_job(0, keep_outputs=False)
        finally:
            self.probe.warming = False

    def free(self):
        """Drop every device buffer this cell holds."""
        del self.engine, self.server_params, self.client_stack


def _job_keys(base, job, n):
    k = jax.random.fold_in(jax.random.fold_in(base, 2), job)
    return jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(n))


def record(res, reqs, keep_outputs: bool) -> JobRecord:
    """Latencies, counts and (host) outputs of one served job."""
    qw, xc, x0 = [], [], []
    failed = 0
    outputs = {}
    for r in reqs:
        stages = {e["stage"]: e["wall"] for e in res.timelines.get(r.req_id,
                                                                   [])}
        comp = res.completions.get(r.req_id)
        ok = (comp is not None and comp.client_finished
              and all(s in stages for s in (QUEUED, ADMITTED, RETIRED,
                                            FINISHED))
              and bool(np.isfinite(comp.x_mid).all())
              and bool(np.isfinite(comp.x0).all()))
        if not ok:
            failed += 1
            continue
        qw.append(stages[ADMITTED] - stages[QUEUED])
        xc.append(stages[RETIRED] - stages[QUEUED])
        x0.append(stages[FINISHED] - stages[QUEUED])
        if keep_outputs:
            outputs[r.req_id] = {"request": r, "x_c": comp.x_mid,
                                 "x_0": comp.x0}
    s = res.summary
    return JobRecord(images=sum(r.batch for r in reqs), ticks=s["ticks"],
                     utilization_mean=s["utilization_mean"],
                     queue_wait_s=qw, xc_s=xc, x0_s=x0,
                     attempted=len(reqs), failed=failed, outputs=outputs)


def p95(values) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(values)
    return float(v[max(0, int(np.ceil(0.95 * len(v))) - 1)])


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")
