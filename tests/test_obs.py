"""repro.obs: tracer / registry / timelines units, the zero-cost Null
singletons, the ServeMetrics regressions (auto-start _now, one CutPlan per
request, rejects-only admission summary), exact vs window-start-approximate
utilization, and the engine/trainer end-to-end obs integration (obs off ==
obs on bitwise; Chrome trace-event schema; one dispatch span per window;
per-request lifecycles with exact finish ticks; the spans on a
``jax.profiler`` capture's host plane; the layer scopes in the compiled
programs; the finisher's lane-step counters)."""
import glob
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (DEFAULT_BUCKETS, NULL_OBS, NULL_REGISTRY, NULL_TRACER,
                       MetricsRegistry, NullTracer, Observability, ObsConfig,
                       TimelineRecorder, Tracer, load_trace, merge_traces,
                       read_jsonl, resolve_obs, validate_events)
from repro.serve import EngineConfig, Request, ServeEngine, ServeMetrics
from repro.serve.metrics import admission_summary

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

T = 10
SIZE = 6
SHAPE = (SIZE, SIZE, 1)


def _init_fn(key):
    d = SIZE * SIZE
    ks = jax.random.split(key, 2)
    return {"w1": jax.random.normal(ks[0], (d + 8, 32)) / 6.0,
            "w2": jax.random.normal(ks[1], (32, d)) / 6.0}


def _apply_fn(p, x, t):
    b = x.shape[0]
    freqs = jnp.exp(jnp.linspace(0.0, 3.0, 4))
    ang = t[:, None].astype(jnp.float32) * freqs[None]
    temb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
    h = jax.nn.silu(jnp.concatenate([x.reshape(b, -1), temb], -1) @ p["w1"])
    return (h @ p["w2"]).reshape(x.shape)


def _requests(n):
    return [Request(req_id=i, key=jax.random.fold_in(jax.random.PRNGKey(7),
                                                     i),
                    batch=1 + i % 2, cut_ratio=(0.25, 0.5, 0.75)[i % 3],
                    arrival_tick=i % 3)
            for i in range(n)]


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
class TestTracer:
    def test_span_records_complete_event(self):
        tr = Tracer()
        with tr.span("work", cat="test", n=3):
            pass
        evs = [e for e in tr.events() if e["ph"] == "X"]
        assert len(evs) == 1
        e = evs[0]
        assert e["name"] == "work" and e["cat"] == "test"
        assert e["dur"] >= 0 and e["args"]["n"] == 3
        validate_events(tr.events())

    def test_decorator_and_instant_and_counter(self):
        tr = Tracer()

        @tr.trace("fn")
        def fn(x):
            return x + 1

        assert fn(1) == 2
        tr.instant("mark", detail="x")
        tr.counter("occupancy", lanes=4, queued=2)
        phases = {e["ph"] for e in tr.events()}
        assert {"X", "i", "C"} <= phases
        validate_events(tr.events())

    def test_async_track_and_export_roundtrip(self, tmp_path):
        tr = Tracer(pid=3, process_name="hostA")
        tr.async_begin("req0", id=0)
        tr.async_instant("req0", id=0, stage="scored")
        tr.async_end("req0", id=0)
        p = tmp_path / "t.json"
        tr.export(str(p))
        evs = load_trace(str(p))
        assert validate_events(evs) == len(evs)
        assert all(e["pid"] == 3 for e in evs)
        assert [e["ph"] for e in evs if e["ph"] in "bie"] == ["b", "i", "e"]
        # the file is plain Chrome trace-event JSON (object form)
        with open(p) as f:
            raw = json.load(f)
        assert "traceEvents" in raw

    def test_clear_keeps_process_metadata(self):
        tr = Tracer(process_name="svc")
        with tr.span("x"):
            pass
        tr.clear()
        assert all(e["ph"] == "M" for e in tr.events())
        assert len(tr.events()) == 2

    def test_merge_traces_unions_pids(self, tmp_path):
        paths = []
        for pid in (0, 1):
            tr = Tracer(pid=pid, process_name=f"host{pid}")
            with tr.span("dispatch", host=pid):
                pass
            p = tmp_path / f"trace.host{pid}"
            tr.export(str(p))
            paths.append(str(p))
        out = tmp_path / "merged.json"
        n = merge_traces(paths, str(out))
        merged = load_trace(str(out))
        assert validate_events(merged) == len(merged) == n
        assert {e["pid"] for e in merged} == {0, 1}

    def test_validate_rejects_malformed(self):
        with pytest.raises(AssertionError):
            validate_events([{"name": "x", "ph": "Z", "pid": 0, "tid": 0,
                             "ts": 0.0}])
        with pytest.raises(AssertionError):
            validate_events([{"ph": "i", "pid": 0, "tid": 0, "ts": 0.0}])

    def test_span_annotates_the_profile(self, monkeypatch):
        import repro.obs.trace as trace_mod
        names = []
        real = trace_mod.TraceAnnotation
        monkeypatch.setattr(trace_mod, "TraceAnnotation",
                            lambda name: names.append(name) or real(name))
        tr = Tracer()
        with tr.span("dispatch"):
            with tr.span("round", cat="train"):
                pass
        assert names == ["serve.dispatch", "train.round"]
        assert [e["name"] for e in tr.events() if e["ph"] == "X"] == \
            ["round", "dispatch"]

    def test_null_tracer_is_free_and_falsy(self):
        assert not NULL_TRACER and isinstance(NULL_TRACER, NullTracer)
        s1 = NULL_TRACER.span("a", big=list(range(10)))
        s2 = NULL_TRACER.span("b")
        assert s1 is s2                     # shared no-op context manager
        with s1:
            pass
        NULL_TRACER.instant("x")
        NULL_TRACER.async_begin("y", id=0)
        assert NULL_TRACER.events() == []


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_monotone(self):
        reg = MetricsRegistry()
        c = reg.counter("jobs_total", "jobs")
        c.inc()
        c.inc(4)
        with pytest.raises(AssertionError):
            c.inc(-1)
        snap = reg.snapshot()
        assert snap["jobs_total"]["kind"] == "counter"
        assert snap["jobs_total"]["series"][0]["value"] == 5

    def test_labels_and_reregistration_checks(self):
        reg = MetricsRegistry()
        c = reg.counter("actions_total", "acts", labels=("action",))
        c.labels(action="admit").inc(2)
        c.labels(action="bump").inc()
        c2 = reg.counter("actions_total", "acts", labels=("action",))
        assert c2 is c                      # same instrument, cached
        with pytest.raises(AssertionError):
            reg.gauge("actions_total", "wrong kind")
        series = {tuple(sorted(s["labels"].items())): s["value"]
                  for s in reg.snapshot()["actions_total"]["series"]}
        assert series[(("action", "admit"),)] == 2
        assert series[(("action", "bump"),)] == 1

    def test_histogram_buckets_cumulative(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", "latency", buckets=(1, 5, 10))
        for v in (0.5, 3, 7, 100):
            h.observe(v)
        s = reg.snapshot()["lat"]["series"][0]["value"]
        assert s["buckets"] == [1.0, 5.0, 10.0]
        assert s["counts"] == [1, 1, 1, 1]      # per-bin + the +inf tail
        assert s["count"] == 4 and s["sum"] == pytest.approx(110.5)
        assert DEFAULT_BUCKETS == tuple(sorted(DEFAULT_BUCKETS))

    def test_jsonl_roundtrip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("ticks_total", "ticks").inc(8)
        p = tmp_path / "m.jsonl"
        reg.write_jsonl(str(p), host=0, window=1)
        reg.counter("ticks_total", "ticks").inc(8)
        reg.write_jsonl(str(p), host=0, window=2, final=True)
        lines = read_jsonl(str(p))
        assert len(lines) == 2 and lines[-1]["final"]
        assert lines[0]["metrics"]["ticks_total"]["series"][0]["value"] == 8
        assert lines[1]["metrics"]["ticks_total"]["series"][0]["value"] == 16
        assert all("ts" in ln for ln in lines)

    def test_null_registry_free_and_falsy(self):
        assert not NULL_REGISTRY
        c = NULL_REGISTRY.counter("x", "y")
        c.inc(5)
        NULL_REGISTRY.histogram("h", "z").observe(1)
        assert NULL_REGISTRY.gauge("g", "w") is c   # one shared no-op
        assert NULL_REGISTRY.snapshot() == {}


# ---------------------------------------------------------------------------
# timelines
# ---------------------------------------------------------------------------
class TestTimelines:
    def test_stage_order_and_details(self):
        tl = TimelineRecorder()
        tl.record(0, "queued", tick=0, batch=2)
        tl.record(0, "admitted", tick=1)
        tl.record(0, "retired", tick=8, exact_tick=6)
        assert tl.stages_of(0) == ["queued", "admitted", "retired"]
        assert tl.of(0)[0]["batch"] == 2
        assert tl.of(0)[-1]["exact_tick"] == 6
        assert all("wall" in e for e in tl.of(0))

    def test_stage_never_twice_and_unknown_rejected(self):
        tl = TimelineRecorder()
        tl.record(1, "queued")
        with pytest.raises(AssertionError):
            tl.record(1, "queued")
        with pytest.raises(AssertionError):
            tl.record(1, "warp")

    def test_reset_allows_reused_req_ids(self):
        tl = TimelineRecorder()
        tl.record(0, "queued")
        tl.reset()
        tl.record(0, "queued")              # fresh serve(), same req_id
        assert set(tl.snapshot()) == {0}

    def test_mirrors_async_events_onto_tracer(self):
        tr = Tracer()
        tl = TimelineRecorder(tracer=tr)
        tl.record(0, "queued")
        tl.record(0, "first_tick", tick=3)
        tl.record(0, "retired", tick=5)
        tl.record(0, "client_finished")
        phs = [e["ph"] for e in tr.events() if e["ph"] in "bie"]
        assert phs == ["b", "i", "e", "i"]
        validate_events(tr.events())


# ---------------------------------------------------------------------------
# Observability bundle
# ---------------------------------------------------------------------------
class TestObservability:
    def test_resolve_and_truthiness(self):
        assert resolve_obs(None) is NULL_OBS and not NULL_OBS
        obs = resolve_obs(ObsConfig())
        assert isinstance(obs, Observability) and obs
        assert resolve_obs(obs) is obs
        with pytest.raises(TypeError):
            resolve_obs("yes please")

    def test_null_obs_surface(self):
        NULL_OBS.request(0, "queued", tick=0)
        assert NULL_OBS.tracer is NULL_TRACER
        assert NULL_OBS.registry is NULL_REGISTRY
        assert NULL_OBS.trace_path_for_host(2) is None

    def test_per_host_trace_paths(self, tmp_path):
        p = str(tmp_path / "trace.json")
        solo = Observability(ObsConfig(trace_path=p))
        assert solo.trace_path_for_host(1) == p
        pod = Observability(ObsConfig(trace_path=p), host_id=1)
        assert pod.trace_path_for_host(2) == p + ".host1"
        assert pod.tracer.events()[0]["pid"] == 1

    def test_config_validation(self):
        with pytest.raises(AssertionError):
            ObsConfig(metrics_every=0)


# ---------------------------------------------------------------------------
# ServeMetrics regressions + edge paths
# ---------------------------------------------------------------------------
class TestServeMetrics:
    def test_now_autostarts_instead_of_absolute_clock(self):
        m = ServeMetrics(capacity=4)
        assert m._t0 is None
        m.on_admit(0, tick=0)               # start() never called
        assert m._t0 is not None
        # the old `self._t0 or 0.0` fallback recorded ~process-uptime
        # absolute values here; post-fix the first event is ~0 relative
        assert 0.0 <= m._admit[0]["wall"] < 1.0

    def test_summary_builds_one_cutplan_per_request(self, monkeypatch):
        import repro.serve.metrics as metrics_mod
        real = metrics_mod.CutPlan
        calls = []
        monkeypatch.setattr(metrics_mod, "CutPlan",
                            lambda *a, **k: calls.append(a) or real(*a, **k))
        m = ServeMetrics(capacity=4)
        reqs = _requests(3)
        for r in reqs:
            m.on_admit(r.req_id, 0)
            m.on_retire(r.req_id, 5)
        m.summary(1.0, T, 1e6, reqs)
        assert len(calls) == len(reqs)      # was 2 per request

    def test_empty_requests_summary(self):
        m = ServeMetrics(capacity=4)
        s = m.summary(1.0, T, 1e6, [])
        assert s["requests"] == 0 and s["served"] == 0
        assert s["utilization_mean"] == 0.0
        assert s["latency_ticks_p95"] == 0.0 and s["client_fraction"] == 0.0

    def test_rejects_only_admission_summary_and_report(self, capsys):
        from repro.serve.admission import AdmissionDecision
        ds = [AdmissionDecision(req_id=i, sampler="ddpm", cut_ratio=0.5,
                                nominal_cut=5, effective_cut=-1, kid=0.0,
                                min_kid=9.9, action="reject")
              for i in range(3)]
        rec = admission_summary(ds)
        assert rec["rejected"] == 3 and "disclosure_kid" not in rec
        # the report renderer must not KeyError on the absent key
        from benchmarks.report import privacy_table
        privacy_table({"n_requests": 3, "cut_ratios": [0.5], "slots": 4,
                       "T": T, "K": 5, "calib": 8, "min_kid": 9.9,
                       "admission": rec, "ticks_gated": 0,
                       "ticks_ungated": 7, "ticks_ratio": 0.0,
                       "equivalence": "n/a"})
        out = capsys.readouterr().out
        assert "| 0 | 3 |" in out.replace("| 0 | 0 ", "| 0 ")

    def test_admission_summary_publishes_action_counters(self):
        from repro.serve.admission import AdmissionDecision
        reg = MetricsRegistry()
        ds = [AdmissionDecision(req_id=0, sampler="ddpm", cut_ratio=0.5,
                                nominal_cut=5, effective_cut=5, kid=1.0,
                                min_kid=0.5, action="admit"),
              AdmissionDecision(req_id=1, sampler="ddpm", cut_ratio=0.5,
                                nominal_cut=5, effective_cut=3, kid=0.9,
                                min_kid=0.5, action="bump")]
        rec = admission_summary(ds, registry=reg)
        assert rec["admitted"] == 1 and rec["bumped"] == 1
        series = reg.snapshot()["serve_admission_actions_total"]["series"]
        vals = {s["labels"]["action"]: s["value"] for s in series}
        assert vals == {"admit": 1, "bump": 1, "reject": 0}

    def test_on_idle_gap(self):
        m = ServeMetrics(capacity=4)
        m.on_idle_gap(0)
        m.on_idle_gap(5)
        m.on_idle_gap(2)
        assert m.summary(1.0, T, 1e6, [])["idle_ticks"] == 7

    def test_boundary_lag_percentiles(self):
        m = ServeMetrics(capacity=4)
        for lag in (0, 1, 3, 7):
            m.on_boundary_lag(lag)
        s = m.summary(1.0, T, 1e6, [])
        assert s["boundary_lag_p100"] == 7
        assert s["boundary_lag_mean"] == pytest.approx(11 / 4)
        m2 = ServeMetrics(capacity=4)
        assert "boundary_lag_p100" not in m2.summary(1.0, T, 1e6, [])

    def test_exact_vs_window_start_utilization(self):
        # 4 active at window start, k=4, lanes latch at ticks 1 and 3:
        # exact per-tick active = [4, 4, 3, 3] (active THROUGH the finish
        # tick inclusive); the window-start approximation says 4 for all
        approx = ServeMetrics(capacity=4)
        approx.on_window(4, 4)
        exact = ServeMetrics(capacity=4)
        exact.on_window_exact(4, [0, 1, 0, 1])
        assert approx._util == [1.0] * 4
        assert exact._util == [1.0, 1.0, 0.75, 0.75]
        assert exact.ticks == approx.ticks == 4
        with pytest.raises(AssertionError):
            exact.on_window_exact(1, [1, 1, 0, 0])   # more done than active

    def test_exact_publishes_trailing_active_gauge(self):
        reg = MetricsRegistry()
        m = ServeMetrics(capacity=4, registry=reg)
        m.on_window_exact(4, [0, 1, 0, 1])
        snap = reg.snapshot()
        assert snap["serve_active_lanes"]["series"][0]["value"] == 2
        assert snap["serve_ticks_total"]["series"][0]["value"] == 4


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def world():
    from repro.diffusion.schedule import cosine_schedule
    return cosine_schedule(T), _init_fn(jax.random.PRNGKey(0))


def _client_stack(n):
    from repro.optim import adamw
    return adamw.tree_stack(
        [_init_fn(kk) for kk in jax.random.split(jax.random.PRNGKey(1), n)])


def _engine(world, obs, **kw):
    sched, server = world
    kw.setdefault("slots", 4)
    kw.setdefault("ticks_per_dispatch", 3)
    kw.setdefault("async_depth", 2)
    cfg = EngineConfig(sched=sched, apply_fn=_apply_fn, image_shape=SHAPE,
                       obs=obs, **kw)
    return ServeEngine(cfg, server)


class TestEngineObs:
    def test_obs_off_matches_obs_on_bitwise(self, world, tmp_path):
        res_off = _engine(world, None).serve(_requests(6))
        obs = ObsConfig(trace_path=str(tmp_path / "trace.json"),
                        metrics_path=str(tmp_path / "m.jsonl"))
        res_on = _engine(world, obs).serve(_requests(6))
        assert set(res_on.completions) == set(res_off.completions)
        for rid, comp in res_off.completions.items():
            np.testing.assert_array_equal(res_on.completions[rid].x_mid,
                                          comp.x_mid)
        assert res_on.summary["ticks"] == res_off.summary["ticks"]
        assert (res_on.summary["utilization_mean"] ==
                res_off.summary["utilization_mean"])
        assert res_off.timelines == {}

    def test_trace_schema_and_span_per_window(self, world, tmp_path):
        path = str(tmp_path / "trace.json")
        eng = _engine(world, ObsConfig(trace_path=path))
        res = eng.serve(_requests(6))
        evs = load_trace(path)
        assert validate_events(evs) == len(evs)
        dispatch = [e for e in evs
                    if e.get("ph") == "X" and e["name"] == "dispatch"]
        assert len(dispatch) == res.summary["windows"]
        names = {e["name"] for e in evs if e.get("ph") == "X"}
        assert {"sync_wait", "retire", "admit"} <= names

    def test_timelines_lifecycle_and_exact_ticks(self, world):
        k = 3
        res = _engine(world, ObsConfig(trace=False),
                      ticks_per_dispatch=k).serve(_requests(6))
        assert set(res.timelines) == set(range(6))
        for rid, tl in res.timelines.items():
            stages = [e["stage"] for e in tl]
            assert stages[0] == "queued"
            assert stages.index("admitted") < stages.index("first_tick") \
                < stages.index("retired")
            ret = tl[stages.index("retired")]
            comp = res.completions[rid]
            assert ret["tick"] == comp.retire_tick
            # exact finish from the done stack: within the window ending
            # at the retire boundary
            assert 0 <= ret["tick"] - ret["exact_tick"] <= k - 1

    def test_client_finished_stage_lands(self, world):
        res = _engine(world, ObsConfig(trace=False)).serve(
            _requests(4), _client_stack(2))
        for rid, tl in res.timelines.items():
            assert tl[-1]["stage"] == "client_finished"
            assert res.completions[rid].client_finished

    def test_metrics_jsonl_written_at_boundaries(self, world, tmp_path):
        p = str(tmp_path / "m.jsonl")
        res = _engine(world, ObsConfig(trace=False, metrics_path=p,
                                       metrics_every=2)).serve(_requests(6))
        lines = read_jsonl(p)
        assert lines and lines[-1]["final"]
        assert all(ln["host"] == 0 for ln in lines)
        names = set(lines[-1]["metrics"])
        assert {"serve_ticks_total", "serve_retired_total",
                "serve_latency_ticks", "serve_queue_depth",
                "serve_active_lanes"} <= names
        retired = lines[-1]["metrics"]["serve_retired_total"]
        assert retired["series"][0]["value"] == res.summary["served"]

    def test_profile_holds_one_dispatch_per_window(self, world, tmp_path):
        """Served under ``jax.profiler.trace``, the host plane holds the
        engine's spans: one ``serve.dispatch`` per dispatched window,
        each inside a ``serve.window``, with the loop's other phases
        nested in windows too."""
        from jax.profiler import ProfileData
        eng = _engine(world, ObsConfig(trace=True, timelines=False))
        stack = _client_stack(2)
        eng.serve(_requests(6), stack)               # compile outside
        with jax.profiler.trace(str(tmp_path)):
            res = eng.serve(_requests(6), stack)
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        spans = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("serve."):
                            spans.setdefault(e.name, []).append(
                                (e.start_ns, e.start_ns + e.duration_ns))
        windows, dispatch = spans["serve.window"], spans["serve.dispatch"]
        assert len(dispatch) == res.summary["windows"] > 0

        def inside(span):
            return [w for w in windows
                    if w[0] <= span[0] and span[1] <= w[1]]
        assert all(len(inside(d)) == 1 for d in dispatch)
        assert all(sum(w[0] <= d[0] and d[1] <= w[1] for d in dispatch)
                   <= 1 for w in windows)
        for name in ("serve.admit", "serve.admit_device", "serve.sync_wait",
                     "serve.retire", "serve.retire_rows"):
            assert spans[name] and all(inside(s) for s in spans[name]), name
        for name in ("serve.enqueue", "serve.finish_drain",
                     "serve.client_finish_dispatch", "serve.finish_pack",
                     "serve.client_finish_sync", "serve.finish_wait"):
            assert spans[name], name

    def test_obs_off_builds_no_annotation(self, world, monkeypatch):
        import repro.obs.trace as trace_mod
        built = []
        real = trace_mod.TraceAnnotation
        monkeypatch.setattr(trace_mod, "TraceAnnotation",
                            lambda name: built.append(name) or real(name))
        stack = _client_stack(2)
        off = _engine(world, None).serve(_requests(6), stack)
        assert built == []
        on = _engine(world, ObsConfig(trace=True)).serve(_requests(6), stack)
        assert "serve.window" in built and "serve.finish_wait" in built
        assert set(on.completions) == set(off.completions)
        for rid, comp in off.completions.items():
            np.testing.assert_array_equal(on.completions[rid].x_mid,
                                          comp.x_mid)
            np.testing.assert_array_equal(on.completions[rid].x0, comp.x0)

    def test_compiled_programs_carry_layer_scopes(self, world):
        """The window and the finisher both run the lane tick, so both
        compiled programs name the U-Net, the noise draw and the step in
        their ops' ``op_name`` metadata."""
        eng = _engine(world, None)
        sched, server = world
        stack = _client_stack(2)
        window = eng._tick.lower(eng._init_state(), server,
                                 eng._menu).compile().as_text()
        shp = (2, 2)
        finish = eng._finish.lower(
            stack, eng._menu, np.zeros(shp + SHAPE, np.float32),
            np.zeros(shp, np.int32), np.full(shp, 5, np.int32),
            np.zeros(shp, np.int32), np.zeros(shp + (2,), np.uint32),
            np.ones(shp, bool)).compile().as_text()
        for text in (window, finish):
            names = [ln.split('op_name="', 1)[1].split('"', 1)[0]
                     for ln in text.splitlines() if 'op_name="' in ln]
            for scope in ("/unet/", "/noise/", "/step/"):
                assert any(scope in n for n in names), scope

    @pytest.mark.parametrize(
        "batches, cuts, clients, waves, programs, dispatched, useful", [
            # a wave per step class (6 client steps at c=0.6 on the dense
            # T=10 chain; 3 at c=0.3), a program per client in each, padded
            # to that client's pow-2 width (two lanes at least) and run for
            # its class's steps
            ((1, 2), (0.3, 0.6), (0, 1), 2, 2, 2 * 3 + 2 * 6,
             1 * 3 + 2 * 6),
            ((2, 2), (0.3, 0.6), (0, 0), 2, 2, 2 * 3 + 2 * 6,
             2 * 3 + 2 * 6),
            ((1, 1), (0.3, 0.6), (0, 1), 2, 2, 2 * 3 + 2 * 6, 3 + 6),
            # two classes x two clients: client 0's three c=0.6 lanes pad
            # to 4; one program a wave over both clients at the widest
            # client's width (the rule before) ran 2 x 4 x 6 + 2 x 2 x 3
            # = 60
            ((1, 2, 3, 1), (0.3, 0.3, 0.6, 0.6), (0, 1, 0, 1), 2, 4,
             2 * 3 + 2 * 3 + 4 * 6 + 2 * 6, 1 * 3 + 2 * 3 + 3 * 6 + 1 * 6),
        ])
    def test_finish_lane_step_counters(self, world, batches, cuts, clients,
                                       waves, programs, dispatched, useful):
        reqs = [Request(req_id=i, key=jax.random.PRNGKey(20 + i), batch=b,
                        cut_ratio=c, client_idx=ci, arrival_tick=0)
                for i, (b, c, ci) in enumerate(zip(batches, cuts, clients))]
        eng = _engine(world, ObsConfig(trace=False))
        res = eng.serve(reqs, _client_stack(2))
        s = res.summary
        assert s["finish_batches"] == waves
        assert s["finish_programs"] == programs
        assert s["finish_lanes"] == sum(batches)
        assert s["finish_lane_steps"] == dispatched
        assert s["finish_useful_lane_steps"] == useful
        snap = eng.obs.registry.snapshot()
        series = snap["serve_finish_lane_steps_total"]["series"]
        assert {x["labels"]["kind"]: x["value"] for x in series} == \
            {"dispatched": dispatched, "useful": useful}
        assert snap["serve_finish_programs_total"]["series"][0]["value"] \
            == programs

    def test_scheduler_aging_promotions_in_summary(self, world):
        from repro.serve import make_scheduler
        res = _engine(world, None,
                      scheduler=make_scheduler("cut_ratio", T)).serve(
            _requests(8))
        assert res.summary["aging_promotions"] >= 0
        res_fifo = _engine(world, None).serve(_requests(8))
        assert res_fifo.summary["aging_promotions"] == 0  # FIFO never ages


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------
class TestTrainerObs:
    def test_train_round_span_and_registry(self):
        from repro.core.trainer import CollaFuseTrainer, TrainerConfig
        cfg = TrainerConfig(n_clients=2, T=8, cut_ratio=0.5)
        tr = CollaFuseTrainer(cfg, _init_fn, _apply_fn, obs=ObsConfig())
        data = [jax.random.normal(k, (2,) + SHAPE)
                for k in jax.random.split(jax.random.PRNGKey(0), 2)]
        tr.train_round(data)
        tr.train_round(data)
        spans = [e for e in tr.obs.tracer.events()
                 if e.get("ph") == "X" and e["name"] == "train_round"]
        assert [s["args"]["round"] for s in spans] == [0, 1]
        snap = tr.obs.registry.snapshot()
        assert snap["train_rounds_total"]["series"][0]["value"] == 2
        assert "train_server_loss" in snap
        validate_events(tr.obs.tracer.events())

    def test_trainer_defaults_to_null_obs(self):
        from repro.core.trainer import CollaFuseTrainer, TrainerConfig
        cfg = TrainerConfig(n_clients=1, T=8, cut_ratio=0.5)
        tr = CollaFuseTrainer(cfg, _init_fn, _apply_fn)
        assert tr.obs is NULL_OBS
