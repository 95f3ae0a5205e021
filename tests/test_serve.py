"""Continuous-batching serving engine: masked per-slot stepping equivalence
with ddpm.sample_range, retire-and-refill under mixed cut-ratios, scheduler
fairness/starvation-freedom, and the masked-step primitive itself."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collafuse
from repro.core.collafuse import CutPlan
from repro.diffusion import ddpm
from repro.diffusion.schedule import cosine_schedule
from repro.optim import adamw
from repro.serve import (CutRatioScheduler, EngineConfig, FIFOScheduler,
                         Request, ServeEngine, make_scheduler,
                         serve_sequential)

T = 12
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


@pytest.fixture(scope="module")
def models():
    sched = cosine_schedule(T)
    server = _init_fn(jax.random.PRNGKey(0))
    stack = adamw.tree_stack(
        [_init_fn(k) for k in jax.random.split(jax.random.PRNGKey(1), 3)])
    return sched, server, stack


def _engine(sched, server, **kw):
    kw.setdefault("slots", 4)
    cfg = EngineConfig(sched=sched, apply_fn=_apply_fn, image_shape=SHAPE,
                       **kw)
    return ServeEngine(cfg, server)


def _check_request_matches_reference(sched, server, stack, comp):
    """Engine lanes ≡ per-image split_sample_lane (same key discipline)."""
    r = comp.request
    plan = CutPlan(T, r.cut_ratio)
    server_fn = functools.partial(_apply_fn, server)
    client_fn = functools.partial(_apply_fn,
                                  adamw.tree_unstack(stack, r.client_idx))
    for i in range(r.batch):
        x0_ref, mid_ref = collafuse.split_sample_lane(
            sched, plan, server_fn, client_fn,
            jax.random.fold_in(r.key, i), SHAPE, return_intermediate=True)
        np.testing.assert_allclose(comp.x_mid[i], np.asarray(mid_ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"x_mid req={r.req_id} lane={i}")
        np.testing.assert_allclose(comp.x0[i], np.asarray(x0_ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"x0 req={r.req_id} lane={i}")


# ---------------------------------------------------------------------------
# masked step primitive
# ---------------------------------------------------------------------------
def test_p_sample_masked_inactive_lanes_bit_unchanged():
    sched = cosine_schedule(T)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (5,) + SHAPE)
    eps = jax.random.normal(jax.random.fold_in(key, 1), x.shape)
    noise = jax.random.normal(jax.random.fold_in(key, 2), x.shape)
    t = jnp.array([5, 0, 3, -2, 1], jnp.int32)    # out-of-range on idle lanes
    active = jnp.array([True, False, True, False, True])
    out = ddpm.p_sample_masked(sched, x, t, eps, noise, active)
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(x[1]))
    np.testing.assert_array_equal(np.asarray(out[3]), np.asarray(x[3]))
    for lane in (0, 2, 4):
        ref = ddpm.denoise_step(sched, x[lane:lane + 1],
                                t[lane:lane + 1], eps[lane:lane + 1],
                                noise[lane:lane + 1])
        np.testing.assert_allclose(np.asarray(out[lane]),
                                   np.asarray(ref[0]), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("backend", ["pallas", "pallas_masked"])
def test_p_sample_masked_backends_agree(backend):
    """The kernel backends reproduce the jnp masked step on active lanes
    (rsqrt-vs-divide rounding only) and bit-identically on inactive ones."""
    sched = cosine_schedule(T)
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (5,) + SHAPE)
    eps = jax.random.normal(jax.random.fold_in(key, 1), x.shape)
    noise = jax.random.normal(jax.random.fold_in(key, 2), x.shape)
    t = jnp.array([T, 0, 3, -2, 1], jnp.int32)
    active = jnp.array([True, False, True, False, True])
    ref = ddpm.p_sample_masked(sched, x, t, eps, noise, active)
    out = ddpm.p_sample_masked(sched, x, t, eps, noise, active,
                               backend=backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    for lane in (1, 3):
        np.testing.assert_array_equal(np.asarray(out[lane]),
                                      np.asarray(x[lane]))


# ---------------------------------------------------------------------------
# engine ≡ sample_range per request (the tentpole equivalence gate)
# ---------------------------------------------------------------------------
def test_engine_matches_sample_range_per_request(models):
    sched, server, stack = models
    reqs = [Request(req_id=0, key=jax.random.PRNGKey(100), batch=2,
                    cut_ratio=0.25, client_idx=0),
            Request(req_id=1, key=jax.random.PRNGKey(101), batch=1,
                    cut_ratio=0.5, client_idx=1, arrival_tick=2),
            Request(req_id=2, key=jax.random.PRNGKey(102), batch=3,
                    cut_ratio=0.75, client_idx=2)]
    eng = _engine(sched, server, scheduler=CutRatioScheduler(T))
    res = eng.serve(list(reqs), stack)
    assert set(res.completions) == {0, 1, 2}
    for comp in res.completions.values():
        _check_request_matches_reference(sched, server, stack, comp)


@pytest.mark.parametrize("backend", ["jnp", "pallas", "pallas_masked"])
def test_engine_step_backends_match_reference(models, backend):
    """The engine produces reference-equivalent lanes under EVERY step
    backend — the fused masked tick included (taken once at __init__)."""
    sched, server, stack = models
    reqs = [Request(req_id=0, key=jax.random.PRNGKey(800), batch=2,
                    cut_ratio=0.5, client_idx=1),
            Request(req_id=1, key=jax.random.PRNGKey(801), batch=1,
                    cut_ratio=0.25, client_idx=0, arrival_tick=1)]
    eng = _engine(sched, server, step_backend=backend)
    assert eng.backend.name == backend
    res = eng.serve(list(reqs), stack)
    for comp in res.completions.values():
        _check_request_matches_reference(sched, server, stack, comp)


def test_engine_edge_cut_ratios(models):
    """c=1 (zero server steps: x_mid == x_T, all-client finish) and c=0
    (server runs the whole chain, finisher is a no-op)."""
    sched, server, stack = models
    reqs = [Request(req_id=0, key=jax.random.PRNGKey(200), cut_ratio=1.0),
            Request(req_id=1, key=jax.random.PRNGKey(201), cut_ratio=0.0,
                    client_idx=2)]
    res = _engine(sched, server).serve(list(reqs), stack)
    for comp in res.completions.values():
        _check_request_matches_reference(sched, server, stack, comp)
    # c=1: the disclosed tensor is pure noise x_T, drawn from k_init
    k_init, _, _ = collafuse.lane_keys(reqs[0].key, 1)
    x_T = jax.random.normal(k_init[0], SHAPE, jnp.float32)
    np.testing.assert_array_equal(res.completions[0].x_mid[0],
                                  np.asarray(x_T))
    # c=0: nothing left for the client, x0 == x_mid
    np.testing.assert_array_equal(res.completions[1].x0,
                                  res.completions[1].x_mid)


def test_engine_matches_sequential_split_sample_outputs(models):
    """serve_sequential (the benchmark baseline) and the engine agree on
    shapes/finiteness; per-lane numerics are covered by the reference
    equivalence (the baseline uses batch-shaped request keys, the engine
    per-lane keys — same distribution, different draws)."""
    sched, server, stack = models
    reqs = [Request(req_id=i, key=jax.random.PRNGKey(300 + i), batch=1,
                    cut_ratio=c, client_idx=i % 3)
            for i, c in enumerate((0.25, 0.5, 0.75))]
    cfg = EngineConfig(sched=sched, apply_fn=_apply_fn, image_shape=SHAPE,
                       slots=4)
    res = ServeEngine(cfg, server).serve(list(reqs), stack)
    outs = serve_sequential(cfg, reqs, server, stack)
    for r in reqs:
        x0_seq, mid_seq = outs[r.req_id]
        comp = res.completions[r.req_id]
        assert comp.x0.shape == x0_seq.shape
        assert comp.x_mid.shape == mid_seq.shape
        assert np.isfinite(comp.x0).all()
        assert bool(jnp.isfinite(x0_seq).all())


# ---------------------------------------------------------------------------
# retire-and-refill under mixed cut-ratios
# ---------------------------------------------------------------------------
def test_retire_and_refill_mixed_cut_ratios(models):
    """More demand than capacity: freed slots are refilled mid-flight, every
    request completes, and outputs still match the per-lane reference."""
    sched, server, stack = models
    reqs = [Request(req_id=i, key=jax.random.PRNGKey(400 + i),
                    batch=1 + i % 2, cut_ratio=(0.25, 0.5, 0.75)[i % 3],
                    client_idx=i % 3)
            for i in range(7)]                    # 10 lanes onto 3 slots
    eng = _engine(sched, server, slots=3, scheduler=FIFOScheduler())
    res = eng.serve(list(reqs), stack)
    assert set(res.completions) == set(range(7))
    s = res.summary
    # refill really happened: serving 10 lanes on 3 slots needs ticks well
    # beyond one request's chain, and utilization accounts multiple waves
    assert s["ticks"] > CutPlan(T, 0.25).n_server_steps
    assert 0.0 < s["utilization_mean"] <= 1.0
    for comp in res.completions.values():
        _check_request_matches_reference(sched, server, stack, comp)


def test_cut_ratio_scheduler_prefers_short_server_jobs(models):
    """Same arrival tick, one free slot at a time: SJF admits/retires the
    high-c (cheap) request first; FIFO keeps arrival order."""
    sched, server, _ = models
    def reqs():
        return [Request(req_id=0, key=jax.random.PRNGKey(500),
                        cut_ratio=0.25),          # 9 server steps
                Request(req_id=1, key=jax.random.PRNGKey(501),
                        cut_ratio=0.75)]          # 3 server steps
    r_sjf = _engine(sched, server, slots=1,
                    scheduler=CutRatioScheduler(T)).serve(reqs())
    r_fifo = _engine(sched, server, slots=1,
                     scheduler=FIFOScheduler()).serve(reqs())
    assert r_sjf.completions[1].retire_tick < r_sjf.completions[0].retire_tick
    assert (r_fifo.completions[0].admit_tick <
            r_fifo.completions[1].admit_tick)


# ---------------------------------------------------------------------------
# starvation-freedom
# ---------------------------------------------------------------------------
def test_cut_ratio_scheduler_ages_out_starvation():
    """Pure scheduler level: a cheap request arriving EVERY tick would
    starve the expensive head under un-aged SJF; aging bounds its wait."""
    sch = CutRatioScheduler(T=100, aging=1.0)
    sch.add(Request(req_id=0, key=None, cut_ratio=0.0, arrival_tick=0))
    admitted_at = None
    for now in range(400):
        sch.add(Request(req_id=1000 + now, key=None, cut_ratio=0.99,
                        arrival_tick=now))
        picked = sch.select(1, now)               # one free slot per tick
        if any(r.req_id == 0 for r in picked):
            admitted_at = now
            break
    # score_head = 100 - wait beats a fresh cheap job's score (1) once
    # wait > 99 — the analytic bound on the admission tick
    assert admitted_at is not None and admitted_at <= 100


def test_cut_ratio_scheduler_no_starvation_for_large_batches():
    """A batch-4 request must not be starved by batch-1 requests slipping
    into every freed slot: once aged to the top of the score order it
    BLOCKS lower-ranked candidates until 4 slots accumulate."""
    sch = CutRatioScheduler(T=100, aging=1.0)
    sch.add(Request(req_id=0, key=None, batch=4, cut_ratio=0.0,
                    arrival_tick=0))
    free, admitted_at = 1, None
    for now in range(400):
        sch.add(Request(req_id=1000 + now, key=None, batch=1,
                        cut_ratio=0.99, arrival_tick=now))
        picked = sch.select(free, now)
        if any(r.req_id == 0 for r in picked):
            admitted_at = now
            break
        # one lane retires per tick; unfilled slots accumulate while the
        # aged head blocks
        free = free - sum(r.batch for r in picked) + 1
    assert admitted_at is not None and admitted_at <= 110


# ---------------------------------------------------------------------------
# SJF fairness: ordering by NOMINAL cost (a bump never buys queue position)
# ---------------------------------------------------------------------------
class _BumpGate:
    """Admission stub: serves everything, bumping the ``"bumpy"`` sampler
    to a cheap effective cut — just enough ``decide`` interface for the
    scheduler to reproduce the SJF fairness inversion without a
    calibration stack."""

    def __init__(self, T, bumped_cut=1):
        self.T, self.bumped_cut = T, bumped_cut

    def decide(self, req):
        from repro.serve.admission import AdmissionDecision
        nominal = int(round((1.0 - req.cut_ratio) * self.T))
        bump = req.sampler == "bumpy"
        return AdmissionDecision(
            req_id=req.req_id, sampler=req.sampler, cut_ratio=req.cut_ratio,
            nominal_cut=nominal,
            effective_cut=self.bumped_cut if bump else nominal,
            kid=0.0, min_kid=-1.0, action="bump" if bump else "admit")


def test_sjf_orders_by_nominal_cost_not_bumped_effective():
    """Regression for the SJF fairness inversion: a privacy bump makes a
    request CHEAPER to execute (``server_cost`` prices the effective cut
    for slot/FLOP accounting) but must not buy it a better queue position
    — under the old effective-cost score a stream of expensive-nominal
    bumped requests perpetually outranked an honest request that asked
    for less."""
    T_ = 100
    sch = CutRatioScheduler(T_, aging=1.0, admission=_BumpGate(T_))
    honest = Request(req_id=0, key=None, cut_ratio=0.95, arrival_tick=0)
    bumped = Request(req_id=1, key=None, cut_ratio=0.0, arrival_tick=0,
                     sampler="bumpy")
    # accounting still prices the bump at its EFFECTIVE (cheap) cut ...
    assert sch.server_cost(bumped) == 1.0 < sch.server_cost(honest)
    # ... but the ordering score is the NOMINAL trajectory cost
    assert sch.nominal_cost(bumped) == pytest.approx(100.0)
    sch.add(bumped)
    sch.add(honest)
    assert [r.req_id for r in sch.select(1, now=0)] == [0]
    # and the bumped request is not starved either: aging admits it once
    # its wait offsets the nominal-cost gap (wait > 100 - 5 ticks)
    admitted_at = None
    for now in range(1, 2 * T_):
        sch.add(Request(req_id=1000 + now, key=None, cut_ratio=0.95,
                        arrival_tick=now))
        if any(r.req_id == 1 for r in sch.select(1, now)):
            admitted_at = now
            break
    assert admitted_at is not None and admitted_at <= T_ + 1


# ---------------------------------------------------------------------------
# wave packing (pack=True)
# ---------------------------------------------------------------------------
def test_fifo_pack_waves_backfill_same_class():
    """pack=True: an admitted head's spare budget back-fills with
    same-class candidates from BEHIND a blocked big request, without ever
    skipping the overall head of the order."""
    def load(sch):
        sch.add(Request(req_id=0, key=None, batch=1, cut_ratio=0.5,
                        arrival_tick=0))
        sch.add(Request(req_id=1, key=None, batch=8, cut_ratio=0.25,
                        arrival_tick=0))
        sch.add(Request(req_id=2, key=None, batch=1, cut_ratio=0.5,
                        arrival_tick=0))
        sch.add(Request(req_id=3, key=None, batch=1, cut_ratio=0.25,
                        arrival_tick=0))
        return sch
    plain = load(FIFOScheduler())
    assert [r.req_id for r in plain.select(2, now=0)] == [0]  # 1 blocks 2,3
    packed = load(FIFOScheduler(pack=True))
    # 2 shares the head's (sampler, cut) class and rides its budget; 3 is
    # a different class and stays queued behind the blocked batch-8
    assert [r.req_id for r in packed.select(2, now=0)] == [0, 2]
    # once the batch-8 request heads the order it blocks EVERYTHING until
    # its slots accumulate — the unpacked liveness rule, unchanged
    assert packed.select(4, now=0) == []
    assert [r.req_id for r in packed.select(8, now=0)] == [1]
    assert [r.req_id for r in packed.select(1, now=0)] == [3]


def test_pack_preserves_large_batch_liveness():
    """Aged batch-4 head under pack=True: back-filling must not let the
    cheap stream starve it — nothing is admitted over its head, so the
    unpacked aging bound carries over unchanged."""
    sch = CutRatioScheduler(T=100, aging=1.0, pack=True)
    sch.add(Request(req_id=0, key=None, batch=4, cut_ratio=0.0,
                    arrival_tick=0))
    free, admitted_at = 1, None
    for now in range(400):
        sch.add(Request(req_id=1000 + now, key=None, batch=1,
                        cut_ratio=0.99, arrival_tick=now))
        picked = sch.select(free, now)
        if any(r.req_id == 0 for r in picked):
            admitted_at = now
            break
        free = free - sum(r.batch for r in picked) + 1
    assert admitted_at is not None and admitted_at <= 110


def test_pack_engine_bitwise_equal_to_unpacked(models):
    """Engine level: pack=True changes only WHEN requests are admitted —
    the completion set and every completion tensor are bitwise the
    unpacked run's (lane numerics depend only on the request key chain)."""
    from repro.diffusion.sampler import make_sampler
    sched, server, _ = models
    samplers = {"ddpm": make_sampler(T),
                "ddim": make_sampler(T, "ddim", 4, eta=0.0)}

    def reqs():
        return [Request(req_id=i, key=jax.random.PRNGKey(800 + i),
                        batch=(1, 4, 1, 2)[i % 4],
                        cut_ratio=(0.25, 0.5)[i % 2],
                        sampler=("ddpm", "ddim")[(i // 2) % 2],
                        arrival_tick=i // 3)
                for i in range(10)]

    runs = {}
    for pack in (False, True):
        eng = _engine(sched, server, slots=4, samplers=samplers,
                      ticks_per_dispatch=3,
                      scheduler=FIFOScheduler(pack=pack))
        runs[pack] = eng.serve(reqs())
    assert set(runs[True].completions) == set(runs[False].completions)
    for rid, comp in runs[False].completions.items():
        np.testing.assert_array_equal(runs[True].completions[rid].x_mid,
                                      comp.x_mid, err_msg=f"req {rid}")


# ---------------------------------------------------------------------------
# dynamic sampler menus (EngineConfig.spare_columns)
# ---------------------------------------------------------------------------
def test_register_sampler_matches_static_menu_bitwise(models):
    """A dynamically registered trajectory serves bit-identically to the
    same sampler in a static menu, and registration adds ZERO compiles —
    the menu is traced data, not a closure constant."""
    from repro.diffusion.sampler import make_sampler
    sched, server, _ = models
    dyn = make_sampler(T, "ddim", 4, eta=0.0)

    def reqs():
        return [Request(req_id=0, key=jax.random.PRNGKey(123), batch=2,
                        cut_ratio=0.5, sampler="dyn")]

    static = _engine(sched, server,
                     samplers={"ddpm": make_sampler(T), "dyn": dyn})
    ref = static.serve(reqs())
    eng = _engine(sched, server, samplers={"ddpm": make_sampler(T)},
                  spare_columns=8)
    eng.serve([Request(req_id=9, key=jax.random.PRNGKey(9),
                       cut_ratio=0.5)])          # compile the tick program
    n_compiled = eng._tick._cache_size()
    tid = eng.register_sampler("dyn", dyn)
    assert eng.registered_samplers() == {"dyn": tid}
    res = eng.serve(reqs())
    assert eng._tick._cache_size() == n_compiled  # no retrace
    np.testing.assert_array_equal(res.completions[0].x_mid,
                                  ref.completions[0].x_mid)


def test_register_sampler_lru_eviction_and_extent_merge(models):
    """When the spare region fills, the LEAST RECENTLY SERVED dynamic
    entry is evicted (registration order is not recency — serving a
    request bumps the stamp), and freed extents merge with their
    neighbours so a full-width trajectory can land after evictions."""
    from repro.diffusion.sampler import make_sampler
    sched, server, _ = models
    eng = _engine(sched, server, samplers={"ddpm": make_sampler(T)},
                  spare_columns=8)
    mk = lambda k: make_sampler(T, "ddim", k, eta=0.0)
    eng.register_sampler("s1", mk(4))
    eng.register_sampler("s2", mk(4))             # spare region now full
    assert set(eng.registered_samplers()) == {"s1", "s2"}
    # serving through s1 bumps its LRU stamp, so s2 — registered later
    # but never used — is the eviction victim
    eng.serve([Request(req_id=0, key=jax.random.PRNGKey(1), sampler="s1")])
    eng.register_sampler("s3", mk(4))
    assert set(eng.registered_samplers()) == {"s1", "s3"}
    # a full-width registration evicts both and needs the two freed
    # 4-column extents MERGED into one 8-column run
    eng.register_sampler("wide", mk(8))
    assert set(eng.registered_samplers()) == {"wide"}
    res = eng.serve([Request(req_id=1, key=jax.random.PRNGKey(2),
                             cut_ratio=0.5, sampler="wide")])
    assert np.isfinite(res.completions[1].x_mid).all()


def test_register_sampler_validation(models):
    """Misuse fails loudly at the registration boundary: no spares, a
    static name, a mismatched schedule, or a trajectory wider than the
    spare region."""
    from repro.diffusion.sampler import make_sampler
    sched, server, _ = models
    eng0 = _engine(sched, server, samplers={"ddpm": make_sampler(T)})
    with pytest.raises(AssertionError, match="spare_columns"):
        eng0.register_sampler("d", make_sampler(T, "ddim", 4, eta=0.0))
    eng = _engine(sched, server, samplers={"ddpm": make_sampler(T)},
                  spare_columns=4)
    with pytest.raises(AssertionError, match="static"):
        eng.register_sampler("ddpm", make_sampler(T))
    with pytest.raises(AssertionError, match="T="):
        eng.register_sampler("d", make_sampler(T + 1))
    with pytest.raises(AssertionError, match="spare columns"):
        eng.register_sampler("d", make_sampler(T, "ddim", 6, eta=0.0))
    # re-registration under the same name replaces the entry in full
    eng.register_sampler("d", mk4 := make_sampler(T, "ddim", 4, eta=0.0))
    tid = eng.register_sampler("d", mk4)
    assert eng.registered_samplers() == {"d": tid}


def test_fragmentation_metrics_surface_in_summary(models):
    """A serve with waiting demand behind a blocked batch head reports
    fragmentation_frac and per-class occupancy in the summary."""
    sched, server, _ = models
    reqs = [Request(req_id=0, key=jax.random.PRNGKey(10), batch=1,
                    cut_ratio=0.25),
            Request(req_id=1, key=jax.random.PRNGKey(11), batch=4,
                    cut_ratio=0.5),
            Request(req_id=2, key=jax.random.PRNGKey(12), batch=1,
                    cut_ratio=0.75)]
    res = _engine(sched, server, slots=4).serve(reqs)
    assert 0.0 <= res.summary["fragmentation_frac"] <= 1.0
    # the batch-4 request cannot ride with the batch-1 head: some free
    # slots enter windows while it waits -> nonzero fragmentation
    assert res.summary["fragmentation_frac"] > 0.0
    occ = res.summary["occupancy_by_class"]
    assert occ and all(v > 0 for v in occ.values())
    assert any(cls.startswith("ddpm@") for cls in occ)


def test_engine_completes_all_requests_within_bound(models):
    """Engine-level liveness: an adversarial mix (staggered arrivals, mixed
    c) fully drains within the engine's own analytic tick bound — run()
    raises if any request is starved past it."""
    sched, server, stack = models
    reqs = [Request(req_id=i, key=jax.random.PRNGKey(600 + i),
                    cut_ratio=(0.0, 0.9, 0.5, 1.0)[i % 4],
                    client_idx=i % 3, arrival_tick=i)
            for i in range(9)]
    for policy in ("fifo", "cut_ratio"):
        res = _engine(sched, server, slots=2,
                      scheduler=make_scheduler(policy, T)).serve(
                          list(reqs), stack)
        assert set(res.completions) == set(range(9)), policy
        for comp in res.completions.values():
            assert comp.x0 is not None and np.isfinite(comp.x0).all()


def test_same_content_requests_do_not_alias_and_dup_ids_rejected(models):
    """Requests compare by identity (eq=False): two same-content requests
    with distinct req_ids are both served; duplicate req_ids are rejected
    at submit (completions/inflight are keyed by req_id)."""
    sched, server, stack = models
    key = jax.random.PRNGKey(900)
    twins = [Request(req_id=i, key=key, cut_ratio=0.5) for i in (0, 1)]
    res = _engine(sched, server).serve(list(twins), stack)
    assert set(res.completions) == {0, 1}
    np.testing.assert_array_equal(res.completions[0].x0,
                                  res.completions[1].x0)
    dups = [Request(req_id=7, key=key), Request(req_id=7, key=key)]
    with pytest.raises(AssertionError, match="duplicate req_id"):
        _engine(sched, server).serve(dups)


def test_fifo_select_respects_head_of_line():
    sch = FIFOScheduler()
    sch.add(Request(req_id=0, key=None, batch=3, arrival_tick=0))
    sch.add(Request(req_id=1, key=None, batch=1, arrival_tick=0))
    assert sch.select(2, now=0) == []             # head (batch 3) blocks
    picked = sch.select(4, now=0)
    assert [r.req_id for r in picked] == [0, 1]
    assert len(sch) == 0


def test_scheduler_respects_arrival_ticks():
    sch = CutRatioScheduler(T)
    sch.add(Request(req_id=0, key=None, arrival_tick=5))
    assert sch.select(4, now=0) == []
    assert sch.next_arrival() == 5
    assert [r.req_id for r in sch.select(4, now=5)] == [0]


# ---------------------------------------------------------------------------
# mesh path (the pjit program serve_diffusion lowers)
# ---------------------------------------------------------------------------
def test_engine_accepts_mesh_and_matches_reference(models):
    from repro.launch.mesh import make_mesh
    sched, server, stack = models
    mesh = make_mesh((1, 1), ("data", "model"))
    reqs = [Request(req_id=0, key=jax.random.PRNGKey(700), batch=2,
                    cut_ratio=0.5, client_idx=1)]
    res = _engine(sched, server, mesh=mesh).serve(list(reqs), stack)
    _check_request_matches_reference(sched, server, stack,
                                     res.completions[0])


def test_slot_specs_shard_lane_axis():
    from repro.launch.mesh import make_mesh
    from repro.models.layers import ShardCtx
    from repro.parallel import sharding as shd
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = ShardCtx(mesh=mesh, batch_axes=("data",))
    P = jax.sharding.PartitionSpec
    state = {"x": jnp.zeros((4,) + SHAPE), "t": jnp.zeros((4,), jnp.int32),
             "key": jnp.zeros((4, 2), jnp.uint32)}
    specs = shd.slot_specs(state, ctx)
    assert specs["x"] == P("data", None, None, None)
    assert specs["t"] == P("data")
    assert specs["key"] == P("data", None)


# ---------------------------------------------------------------------------
# k-tick scan windows + async double-buffering (PR 6 tentpole)
# ---------------------------------------------------------------------------
def _mixed_menu():
    from repro.diffusion.sampler import make_sampler
    return {"ddpm": make_sampler(T),
            "ddim6": make_sampler(T, "ddim", 6, eta=0.0)}


def _mixed_reqs():
    """Mixed DDPM/DDIM traffic, staggered arrivals, batches > 1 — more
    lanes than slots so retire-and-refill happens at window boundaries."""
    return [Request(req_id=i,
                    key=jax.random.fold_in(jax.random.PRNGKey(1234), i),
                    batch=1 + i % 2, cut_ratio=(0.25, 0.5, 0.75)[i % 3],
                    client_idx=i % 3, arrival_tick=i % 5,
                    sampler=("ddpm", "ddim6")[i % 2])
            for i in range(8)]


@pytest.fixture(scope="module")
def gated_mixed_ref(models):
    """(policy floor, reference ServeResult) at k=1/depth=1 with the KID
    gate binding (floor at the ddim profile median -> some requests admit
    at nominal, some bump or reject)."""
    from repro.serve import AdmissionPolicy
    sched, server, stack = models
    calib = jnp.tanh(jax.random.normal(jax.random.PRNGKey(5), (4,) + SHAPE))
    probe = AdmissionPolicy(sched, calib, min_kid=float("-inf"),
                            samplers=_mixed_menu(),
                            server_fn=functools.partial(_apply_fn, server))
    prof = probe.profile("ddim6")
    floor = float(np.median(prof))
    mk_pol = lambda: probe.with_min_kid(floor)
    ref = _engine(sched, server, samplers=_mixed_menu(),
                  admission=mk_pol()).serve(_mixed_reqs(), stack)
    assert any(d.action != "admit" for d in ref.decisions.values()), \
        "fixture floor must actually gate"
    return mk_pol, ref


@pytest.mark.parametrize("k,depth", [(4, 1), (8, 1), (4, 2), (8, 3)])
def test_scan_async_bitwise_equal_to_sync_k1(models, gated_mixed_ref,
                                             k, depth):
    """The tentpole gate: k-tick scan windows and async double-buffering
    change ONLY timing metadata — completions (x_mid AND finished x0) are
    bitwise identical to the synchronous one-tick engine, on mixed
    DDPM/DDIM traffic with the KID admission gate on."""
    sched, server, stack = models
    mk_pol, ref = gated_mixed_ref
    res = _engine(sched, server, samplers=_mixed_menu(), admission=mk_pol(),
                  ticks_per_dispatch=k, async_depth=depth).serve(
                      _mixed_reqs(), stack)
    assert set(res.completions) == set(ref.completions)
    assert res.decisions == ref.decisions
    for rid, comp in ref.completions.items():
        np.testing.assert_array_equal(res.completions[rid].x_mid,
                                      comp.x_mid, err_msg=f"x_mid {rid}")
        np.testing.assert_array_equal(res.completions[rid].x0,
                                      comp.x0, err_msg=f"x0 {rid}")
    assert res.summary.get("boundary_lag_p100", 0) <= k - 1


def test_retire_at_boundary_latency_bound(models):
    """Retirement happens at the scan boundary: the retire tick is
    window-aligned, overshoots the exact finish by at most k-1 ticks
    (p100), and the done stack recovers the exact finish for metrics."""
    sched, server, _ = models
    k = 4
    req = Request(req_id=0, key=jax.random.PRNGKey(77), cut_ratio=0.5)
    cut = CutPlan(T, 0.5).n_server_steps
    assert cut % k != 0, "pick a cut that does NOT land on a boundary"
    res = _engine(sched, server, ticks_per_dispatch=k).serve([req])
    comp = res.completions[0]
    boundary = comp.retire_tick
    assert boundary % k == 0
    assert 0 <= boundary - cut <= k - 1
    assert res.summary["boundary_lag_p100"] == boundary - cut
    assert res.summary["ticks"] == boundary
    assert res.summary["ticks_per_dispatch"] == k


def test_idle_gap_recorded_not_silent(models):
    """An empty engine jumps to the next arrival; the skipped ticks are
    now surfaced in the summary instead of silently disappearing."""
    sched, server, _ = models
    req = Request(req_id=0, key=jax.random.PRNGKey(88), cut_ratio=0.5,
                  arrival_tick=7)
    res = _engine(sched, server).serve([req])
    assert res.summary["idle_ticks"] == 6     # 1..7 jump skips 6 ticks
    assert res.summary["ticks"] == CutPlan(T, 0.5).n_server_steps


# ---------------------------------------------------------------------------
# pod mode: per-host lane ownership over one shared queue (simulated hosts)
# ---------------------------------------------------------------------------
def test_two_simulated_hosts_partition_and_cover_all_lanes(models):
    """Two engines replaying the same queue as pod hosts 0 and 1: each
    materializes exactly its OWNED lanes' cut tensors, ownership is a
    partition (every image row owned by exactly one host), and the union
    reassembles the single-host result bitwise."""
    sched, server, stack = models
    reqs = lambda: [Request(req_id=i,
                            key=jax.random.fold_in(jax.random.PRNGKey(9), i),
                            batch=2, cut_ratio=(0.25, 0.5)[i % 2],
                            client_idx=i % 3,
                            sampler=("ddpm", "ddim6")[i % 2])
                    for i in range(5)]
    menu = _mixed_menu
    ref = _engine(sched, server, samplers=menu()).serve(reqs(), stack)
    hosts = [_engine(sched, server, samplers=menu(), hosts=2, host_id=h,
                     ticks_per_dispatch=2, async_depth=2).serve(
                         reqs(), stack)
             for h in (0, 1)]
    assert all(set(h.completions) == set(ref.completions) for h in hosts)
    for rid, comp in ref.completions.items():
        c0, c1 = hosts[0].completions[rid], hosts[1].completions[rid]
        own0, own1 = c0.owned, c1.owned
        assert ((own0 ^ own1).all()), f"ownership must partition req {rid}"
        merged = np.where(own0[:, None, None, None], c0.x_mid, c1.x_mid)
        np.testing.assert_array_equal(merged, comp.x_mid,
                                      err_msg=f"union x_mid req {rid}")
        # un-owned rows were never materialized on that host
        for c in (c0, c1):
            assert not np.any(c.x_mid[~c.owned])
    # the single-host engine owns everything
    assert all(c.owned.all() for c in ref.completions.values())


@pytest.mark.slow
def test_pod_smoke_two_process_distributed(tmp_path):
    """Real 2-process ``jax.distributed`` run (gloo collectives, one CPU
    device per process): both hosts replay the shared queue, each writes
    its owned rows, and the union reassembles the in-process single-host
    reference bitwise."""
    import json
    import os
    import socket
    import subprocess
    import sys

    from repro.launch import pod_smoke

    with socket.socket() as s:                 # free coordinator port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)                 # one device per process
    procs = [subprocess.Popen(
        [sys.executable, "-m", "repro.launch.pod_smoke",
         "--coordinator", f"127.0.0.1:{port}",
         "--num-processes", "2", "--process-id", str(h),
         "--out", str(tmp_path / f"pod{h}.json")],
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for h in (0, 1)]
    outs = [p.communicate(timeout=240)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]
        assert "pod_smoke OK" in out
    arts = [json.loads((tmp_path / f"pod{h}.json").read_text())
            for h in (0, 1)]

    ref = pod_smoke.artifact(
        pod_smoke.serve_pod(1, 0, slots=8, n_requests=6, k=4, depth=2), 0)
    assert set(ref["completions"]) == set(arts[0]["completions"]) \
        == set(arts[1]["completions"])
    for rid, rc in ref["completions"].items():
        c0, c1 = arts[0]["completions"][rid], arts[1]["completions"][rid]
        assert not set(c0["owned"]) & set(c1["owned"]), rid
        assert sorted(c0["owned"] + c1["owned"]) \
            == sorted(int(i) for i in rc["rows"]), rid
        assert c0["retire_tick"] == c1["retire_tick"] == rc["retire_tick"]
        for i, row in {**c0["rows"], **c1["rows"]}.items():
            assert row == rc["rows"][i], (rid, i)


# ---------------------------------------------------------------------------
# streaming client finisher (PR 8 tentpole): stream ≡ drain, bitwise
# ---------------------------------------------------------------------------
def _finisher_reqs():
    """Mixed DDPM/DDIM, staggered arrivals, repeated client_idx (so finish
    batches group), PLUS local-only c=1.0 requests that complete at
    arrival — every staging path into the finish pipeline."""
    return [Request(req_id=i,
                    key=jax.random.fold_in(jax.random.PRNGKey(4321), i),
                    batch=1 + i % 2,
                    cut_ratio=(0.25, 0.5, 0.75, 1.0)[i % 4],
                    client_idx=i % 3, arrival_tick=i % 5,
                    sampler=("ddpm", "ddim6")[i % 2])
            for i in range(9)]


@pytest.mark.parametrize("k,depth,fdepth", [(1, 1, 1), (4, 2, 1),
                                            (8, 2, 2), (4, 1, 3)])
def test_stream_finish_bitwise_equal_to_drain(models, k, depth, fdepth):
    """The tentpole gate: streaming the client segment against in-flight
    server windows changes ONLY timing — x_mid and x0 are bitwise equal
    to the post-drain reference, because each lane's numerics depend only
    on its key chain, never on which finish batch carried it."""
    sched, server, stack = models
    ref = _engine(sched, server, samplers=_mixed_menu(),
                  ticks_per_dispatch=k, async_depth=depth,
                  finish_mode="drain").serve(_finisher_reqs(), stack)
    res = _engine(sched, server, samplers=_mixed_menu(),
                  ticks_per_dispatch=k, async_depth=depth,
                  finish_mode="stream",
                  finish_async_depth=fdepth).serve(_finisher_reqs(), stack)
    assert set(res.completions) == set(ref.completions)
    for rid, comp in ref.completions.items():
        got = res.completions[rid]
        assert got.client_finished and comp.client_finished
        np.testing.assert_array_equal(got.x_mid, comp.x_mid,
                                      err_msg=f"x_mid {rid}")
        np.testing.assert_array_equal(got.x0, comp.x0,
                                      err_msg=f"x0 {rid}")
    assert ref.summary["finish_mode"] == "drain"
    assert ref.summary["overlap_frac"] == 0.0
    assert res.summary["finish_mode"] == "stream"
    assert 0.0 <= res.summary["overlap_frac"] <= 1.0
    assert res.summary["finish_batches"] >= 1
    assert res.summary["finish_async_depth"] == fdepth


def test_stream_finish_bitwise_under_admission_gate(models,
                                                    gated_mixed_ref):
    """Stream ≡ drain also when the KID gate rewrites cuts (bumped
    requests finish MORE client steps) and rejects requests mid-queue.
    The module reference fixture runs the default stream mode, so a drain
    run against it proves both directions."""
    sched, server, stack = models
    mk_pol, ref = gated_mixed_ref
    res = _engine(sched, server, samplers=_mixed_menu(),
                  admission=mk_pol(), ticks_per_dispatch=8, async_depth=2,
                  finish_mode="drain").serve(_mixed_reqs(), stack)
    assert res.decisions == ref.decisions
    assert set(res.completions) == set(ref.completions)
    for rid, comp in ref.completions.items():
        np.testing.assert_array_equal(res.completions[rid].x0, comp.x0,
                                      err_msg=f"x0 {rid}")


def test_stream_tail_packs_one_program_per_class_and_client(models):
    """A drain tail of 3 step classes x 3 clients: every class ships as
    its own wave and every client of it as its own one-row program,
    padded to that (class, client) group's pow-2 width (two lanes at
    least) and run for the class's own steps — and x0 stays bitwise the
    drain reference's."""
    sched, server, stack = models
    classes = [("ddpm", 0.25), ("ddpm", 0.75), ("ddim6", 0.75)]
    # 6 lanes a class: under the halved wave floor (slots lanes), so no
    # class ships before the tail
    reqs = [Request(req_id=3 * c + ci,
                    key=jax.random.fold_in(jax.random.PRNGKey(77), 3 * c + ci),
                    batch=1 + (c + ci) % 3, cut_ratio=cut, client_idx=ci,
                    arrival_tick=0, sampler=name)
            for c, (name, cut) in enumerate(classes) for ci in range(3)]
    ref = _engine(sched, server, slots=8, samplers=_mixed_menu(),
                  finish_mode="drain").serve(reqs, stack)
    eng = _engine(sched, server, slots=8, samplers=_mixed_menu(),
                  finish_mode="stream")
    jitted, shapes = eng._finish, []

    def recorded(client_stack, menu, x, *rest):
        assert jax.tree.leaves(client_stack)[0].shape[0] == 1
        shapes.append(tuple(x.shape[:2]))
        return jitted(client_stack, menu, x, *rest)
    eng._finish = recorded
    res = eng.serve(reqs, stack)
    pow2 = [max(2, 1 << (r.batch - 1).bit_length()) for r in reqs]
    assert sorted(shapes) == sorted((1, w) for w in pow2)
    steps = [eng._sampler_of(r).K - eng._effective_cut(r) for r in reqs]
    assert len(set(steps)) == len(classes)
    s = res.summary
    assert (s["finish_batches"], s["finish_programs"]) == (3, 9)
    assert s["finish_lane_steps"] == sum(w * n for w, n in zip(pow2, steps))
    assert s["finish_useful_lane_steps"] == sum(
        r.batch * n for r, n in zip(reqs, steps))
    assert set(res.completions) == set(ref.completions)
    for rid, comp in ref.completions.items():
        np.testing.assert_array_equal(res.completions[rid].x0, comp.x0,
                                      err_msg=f"x0 {rid}")


def test_drain_local_batches_one_draw_per_boundary(models):
    """Local-only (c=1.0) requests due at the same boundary share ONE
    vmapped x_T draw — and each lane's slice is bitwise the independent
    per-lane draw the engine's key discipline promises."""
    sched, server, stack = models
    reqs = [Request(req_id=i,
                    key=jax.random.fold_in(jax.random.PRNGKey(31), i),
                    batch=1 + i % 3, cut_ratio=1.0, client_idx=i % 3,
                    arrival_tick=(0, 0, 0, 4)[i])
            for i in range(4)]
    res = _engine(sched, server, samplers=_mixed_menu()).serve(reqs, stack)
    for r in reqs:
        comp = res.completions[r.req_id]
        assert comp.retire_tick >= r.arrival_tick
        k_init, _, k_cli = collafuse.lane_keys(r.key, r.batch)
        x_T = jax.vmap(lambda kk: jax.random.normal(
            kk, SHAPE, jnp.float32))(k_init)
        np.testing.assert_array_equal(comp.x_mid, np.asarray(x_T),
                                      err_msg=f"x_T req {r.req_id}")
        assert comp.client_finished


def test_scheduler_retired_callbacks():
    """on_retired subscribes, notify_retired fans out in subscription
    order, and the returned unsubscriber is idempotent."""
    s = FIFOScheduler()
    seen_a, seen_b = [], []
    unsub_a = s.on_retired(lambda r, t: seen_a.append((r.req_id, t)))
    s.on_retired(lambda r, t: seen_b.append((r.req_id, t)))
    r = Request(req_id=7, key=jax.random.PRNGKey(0), cut_ratio=0.5)
    s.notify_retired(r, 12)
    assert seen_a == [(7, 12)] and seen_b == [(7, 12)]
    unsub_a()
    unsub_a()                      # second call is a no-op, not an error
    s.notify_retired(r, 16)
    assert seen_a == [(7, 12)]
    assert seen_b == [(7, 12), (7, 16)]


def test_warmup_prefix_one_request_per_compile_key():
    """warmup_prefix keeps the FIRST request of every distinct
    (batch, sampler, cut_ratio) compile key and drops the rest."""
    from repro.serve.engine import warmup_prefix
    key = jax.random.PRNGKey(0)
    reqs = [Request(req_id=i, key=jax.random.fold_in(key, i),
                    batch=(1, 2, 1, 2)[i % 4],
                    cut_ratio=(0.5, 0.5, 0.25, 0.5)[i % 4],
                    sampler=("ddpm", "ddpm", "ddpm", "ddim6")[i % 4])
            for i in range(12)]
    prefix = warmup_prefix(reqs)
    keys = [(r.batch, r.sampler, r.cut_ratio) for r in prefix]
    assert len(keys) == len(set(keys)) == 4
    assert [r.req_id for r in prefix] == [0, 1, 2, 3]
    assert warmup_prefix(prefix) == prefix


def test_engine_config_finish_knob_validation(models):
    sched, server, _ = models
    with pytest.raises(AssertionError, match="finish_mode"):
        _engine(sched, server, finish_mode="eager")
    with pytest.raises(AssertionError, match="finish_async_depth"):
        _engine(sched, server, finish_async_depth=0)
    with pytest.raises(AssertionError, match="finish_async_depth"):
        _engine(sched, server, finish_async_depth=33)


# ---------------------------------------------------------------------------
# _host_rows: the non-fully-addressable shard walk (pod fast path)
# ---------------------------------------------------------------------------
class _FakeShard:
    """One addressable shard: index like a real jax Shard (tuple of
    slices, leading slot axis), data = the covered rows."""

    def __init__(self, sl, full):
        self.index = (sl,) + (slice(None),) * (full.ndim - 1)
        self.data = full[sl]


class _FakeShardedArray:
    """Duck-typed globally-sharded array: NOT fully addressable, exposes
    only the shards this host holds."""

    is_fully_addressable = False

    def __init__(self, full, shard_slices):
        self.shape = full.shape
        self.addressable_shards = [_FakeShard(sl, full) for sl in
                                   shard_slices]


def test_host_rows_walks_partial_shards(models):
    """Pod host 0 of 2 over 4 slots owns lanes {0, 1}.  Against a
    non-fully-addressable array it must copy owned rows out of whichever
    addressable shards cover them — including shards whose slice has
    None endpoints — skip shards with no owned hits, and never
    materialize un-owned lanes even when their rows are addressable."""
    sched, server, _ = models
    eng = _engine(sched, server, slots=4, hosts=2, host_id=0)
    assert eng._lane_owned.tolist() == [True, True, False, False]
    full = np.arange(4 * SIZE * SIZE, dtype=np.float32).reshape(
        (4,) + SHAPE)
    # shard layout: [None:2) and [2:None) — boundary lane 1 sits at the
    # first shard's stop-1, lane 2 (un-owned) at the second's start
    arr = _FakeShardedArray(full, [slice(None, 2), slice(2, None)])
    rows = eng._host_rows(arr, [0, 1, 2, 3])
    assert sorted(rows) == [0, 1]
    for ln in (0, 1):
        np.testing.assert_array_equal(rows[ln], full[ln])
    # empty-hit shard: host addresses ONLY rows it doesn't own
    assert eng._host_rows(_FakeShardedArray(full, [slice(2, 4)]),
                          [2, 3]) == {}
    # no owned lanes requested at all -> no shard walk, empty dict
    assert eng._host_rows(arr, [2, 3]) == {}
    # single shard with both endpoints None covers everything
    rows_all = eng._host_rows(_FakeShardedArray(full, [slice(None, None)]),
                              [0, 1, 2, 3])
    assert sorted(rows_all) == [0, 1]
    # and the fully-addressable gather path returns the same rows
    rows_fast = eng._host_rows(jnp.asarray(full), [0, 1, 2, 3])
    assert sorted(rows_fast) == [0, 1]
    for ln in (0, 1):
        np.testing.assert_array_equal(rows_fast[ln], rows[ln])


# ---------------------------------------------------------------------------
# classifier-free guidance: metrics accounting for lane pairs
# ---------------------------------------------------------------------------
NUM_CLASSES = 3


def _apply_fn_cond(p, x, t, y=None):
    b = x.shape[0]
    freqs = jnp.exp(jnp.linspace(0.0, 3.0, 4))
    ang = t[:, None].astype(jnp.float32) * freqs[None]
    temb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
    yc = (jnp.full((b,), NUM_CLASSES, jnp.int32) if y is None
          else jnp.clip(y, 0, NUM_CLASSES))
    temb = temb + p["yemb"][yc]
    h = jax.nn.silu(jnp.concatenate([x.reshape(b, -1), temb], -1) @ p["w1"])
    return (h @ p["w2"]).reshape(x.shape)


def test_guided_pair_counts_once_in_metrics():
    """A guided request's cond+uncond pair is ONE request and ONE image
    per batch lane: ``images``/``requests`` never double-count shadows,
    the occupancy class (keyed sampler@cut@w) burns exactly 2x the
    lane-ticks, and the FLOP split doubles the server segment only."""
    from repro.diffusion.sampler import make_sampler
    sched = cosine_schedule(T)
    d = SIZE * SIZE
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    server = {"w1": jax.random.normal(ks[0], (d + 8, 32)) / 6.0,
              "w2": jax.random.normal(ks[1], (32, d)) / 6.0,
              "yemb": jax.random.normal(ks[2], (NUM_CLASSES + 1, 8)) / 6.0}
    samplers = {"ddpm": make_sampler(T),
                "ddpm_g": make_sampler(T, guidance=1.5)}
    cfg = EngineConfig(sched=sched, apply_fn=_apply_fn_cond,
                       image_shape=SHAPE, slots=4, samplers=samplers,
                       num_classes=NUM_CLASSES)
    eng = ServeEngine(cfg, server)

    def run(name):
        return eng.serve([Request(req_id=0, key=jax.random.PRNGKey(9),
                                  batch=2, cut_ratio=0.5, sampler=name,
                                  label=1)])
    plain, guided = run("ddpm"), run("ddpm_g")
    sp, sg = plain.summary, guided.summary
    # one request, two images — the pair never double-counts
    assert sp["requests"] == sg["requests"] == 1
    assert sp["images"] == sg["images"] == 2
    # server segment exactly doubles; the client finish would not (no
    # client stack here, but the split itself is per-request)
    assert sg["server_flops"] == 2.0 * sp["server_flops"]
    assert sg["client_flops"] == sp["client_flops"]
    # occupancy classes carry the guidance scale and the guided class
    # burns exactly twice the lane-ticks over the same trajectory
    occ_p, occ_g = sp["occupancy_by_class"], sg["occupancy_by_class"]
    cut = CutPlan(T, 0.5).n_server_steps
    assert occ_p == {f"ddpm@{cut}@0": 2 * cut}
    assert occ_g == {f"ddpm_g@{cut}@1.5": 4 * cut}
    assert np.isfinite(np.asarray(guided.completions[0].x_mid)).all()
