"""KID-gated admission: decision determinism, bump-to-noisier monotonicity,
the reject path, scheduler select-gating, engine end-to-end guarantees
(every served disclosure clears the floor; gate off is bitwise the ungated
engine), and the satellite fixes that ride along (sampler-menu agreement,
pow-2 finisher jit cache)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collafuse
from repro.core.collafuse import CutPlan
from repro.diffusion.sampler import (Sampler, assert_same_menu,
                                     make_sampler, sample_trajectory)
from repro.diffusion.schedule import cosine_schedule
from repro.optim import adamw
from repro.serve import (AdmissionPolicy, CutRatioScheduler, EngineConfig,
                         Request, ServeEngine, make_scheduler)

T = 12
K = 5
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


def _menu():
    return {"ddpm": make_sampler(T),
            "ddim": make_sampler(T, "ddim", K, eta=0.0)}


@pytest.fixture(scope="module")
def world():
    sched = cosine_schedule(T)
    server = _init_fn(jax.random.PRNGKey(0))
    stack = adamw.tree_stack(
        [_init_fn(k) for k in jax.random.split(jax.random.PRNGKey(1), 3)])
    calib = jnp.tanh(jax.random.normal(jax.random.PRNGKey(5), (4,) + SHAPE))
    return sched, server, stack, calib


@pytest.fixture(scope="module")
def probe(world):
    """One policy instance whose (sampler, pos) score cache every test
    shares — `with_min_kid` re-derives decisions without re-scoring."""
    sched, server, _, calib = world
    return AdmissionPolicy(sched, calib, min_kid=float("-inf"),
                           samplers=_menu(),
                           server_fn=functools.partial(_apply_fn, server))


def _req(i, c, sampler="ddim", **kw):
    return Request(req_id=i, key=jax.random.fold_in(jax.random.PRNGKey(7), i),
                   cut_ratio=c, sampler=sampler, **kw)


# ---------------------------------------------------------------------------
# scoring: the gate's primitive
# ---------------------------------------------------------------------------
def test_disclosed_at_pos_reproduces_disclosed_at_split(world):
    """At pos == plan.cut_index(sampler) the admission score inspects
    EXACTLY the tensor the protocol disclosed — same key discipline,
    bitwise."""
    sched, server, _, calib = world
    server_fn = functools.partial(_apply_fn, server)
    key = jax.random.PRNGKey(11)
    for c in (0.0, 0.3, 0.7, 1.0):
        plan = CutPlan(T, c)
        smp = _menu()["ddim"]
        ref = collafuse.disclosed_at_split(sched, plan, server_fn, key,
                                           calib, sampler=smp)
        out = collafuse.disclosed_at_pos(sched, smp, server_fn, key, calib,
                                         plan.cut_index(smp))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_scores_deterministic_across_policy_instances(world):
    """Two independently constructed policies (fresh jit caches) score
    identically — decisions are reproducible across processes/runs."""
    sched, server, _, calib = world
    mk = lambda: AdmissionPolicy(
        sched, calib, min_kid=0.05, samplers=_menu(),
        server_fn=functools.partial(_apply_fn, server))
    a, b = mk(), mk()
    assert a.profile("ddim") == b.profile("ddim")
    for c in (0.1, 0.5, 0.9):
        da, db = a.decide(_req(1, c)), b.decide(_req(1, c))
        assert da == db


def test_score_cache_is_per_cut_and_sampler(probe):
    """O(menu x cuts), not O(requests): deciding many requests at the same
    (sampler, cut) computes each position's KID once."""
    pol = probe.with_min_kid(-1.0)
    for i in range(32):
        pol.decide(_req(i, 0.5, sampler="ddim"))
    # only the nominal position was ever scored for this (sampler, cut);
    # the guidance slot of the key is None for unguided samplers
    assert ("ddim", CutPlan(T, 0.5).cut_index(_menu()["ddim"]), None) \
        in pol._kid_cache
    assert len(pol._decision_cache) == 1


# ---------------------------------------------------------------------------
# decisions: admit / bump / reject
# ---------------------------------------------------------------------------
def test_bump_scans_to_first_clearing_noisier_position(probe):
    """The effective cut is the HIGHEST position <= nominal whose KID
    clears the floor — exactly the first stop of the noisier-ward scan."""
    prof = probe.profile("ddim")
    nominal = CutPlan(T, 0.1).cut_index(_menu()["ddim"])
    assert nominal >= 2, "fixture must leave room to bump"
    # pick a floor that fails the nominal but clears some position below
    below = [p for p in range(nominal) if prof[p] > prof[nominal]]
    assert below, "fixture profile must allow a bump"
    floor = (prof[nominal] + max(prof[p] for p in below)) / 2
    d = probe.with_min_kid(floor).decide(_req(0, 0.1))
    assert d.action == "bump" and d.bumped and d.served
    assert d.effective_cut < nominal == d.nominal_cut
    expected = max(p for p in range(nominal + 1) if prof[p] >= floor)
    assert d.effective_cut == expected
    assert d.kid == prof[d.effective_cut] >= floor


def test_bump_monotone_in_floor(probe):
    """Raising the floor never moves the effective cut LESS noisy: the
    served position is non-increasing in min_kid until rejection."""
    prof = probe.profile("ddim")
    cuts = []
    floors = sorted(set(prof)) + [max(prof) + 1.0]
    for f in floors:
        d = probe.with_min_kid(f).decide(_req(0, 0.1))
        cuts.append(d.effective_cut if d.served else -1)
    assert all(a >= b for a, b in zip(cuts, cuts[1:])), (floors, cuts)
    assert cuts[0] == CutPlan(T, 0.1).cut_index(_menu()["ddim"])  # admit all
    assert cuts[-1] == -1                                        # reject all


def test_reject_when_no_position_clears(probe):
    floor = max(probe.profile("ddim")) + 1.0
    d = probe.with_min_kid(floor).decide(_req(3, 0.1))
    assert d.action == "reject" and not d.served
    assert d.effective_cut == -1
    # `kid` records how close the trajectory came to clearing
    assert d.kid == max(probe.profile("ddim")[:d.nominal_cut + 1])


def test_admit_at_nominal_when_floor_clears(probe):
    d = probe.with_min_kid(-1.0).decide(_req(4, 0.5))
    assert d.action == "admit" and d.served and not d.bumped
    assert d.effective_cut == d.nominal_cut == \
        CutPlan(T, 0.5).cut_index(_menu()["ddim"])


def test_policy_rejects_small_calibration_batch(world):
    sched, server, _, _ = world
    one = jnp.zeros((1,) + SHAPE)
    with pytest.raises(AssertionError, match="calibration batch"):
        AdmissionPolicy(sched, one, samplers=_menu())


# ---------------------------------------------------------------------------
# scheduler: the select gate + effective-cut SJF costs
# ---------------------------------------------------------------------------
def test_select_gate_drops_rejected_without_blocking(probe):
    """A rejected request is removed at select, recorded, and does NOT
    head-of-line block the admitted request behind it."""
    prof = probe.profile("ddim")
    pol = probe.with_min_kid(max(prof) + 1.0)    # rejects every ddim cut
    sch = CutRatioScheduler(T, samplers=_menu(), admission=pol)
    sch.add(_req(0, 0.1, batch=1))               # will be rejected
    sch.add(_req(1, 0.5, sampler="ddpm", batch=1))
    picked = sch.select(1, now=0)
    assert [r.req_id for r in picked] == [1] or picked == []
    # ddpm profile may or may not clear; re-derive expectation explicitly
    d_ddpm = pol.decide(_req(1, 0.5, sampler="ddpm"))
    assert ([r.req_id for r in picked] == [1]) == d_ddpm.served
    rej = sch.take_rejections()
    assert 0 in {d.req_id for d in rej}
    assert len(sch) == (0 if d_ddpm.served else 0)


def test_sjf_costs_bumped_requests_at_effective_cut(probe):
    """A bumped request is a cheaper job: SJF must order it by the
    effective (noisier) cut, not the nominal one."""
    prof = probe.profile("ddim")
    nominal = CutPlan(T, 0.1).cut_index(_menu()["ddim"])
    below = [p for p in range(nominal) if prof[p] > prof[nominal]]
    floor = (prof[nominal] + max(prof[p] for p in below)) / 2
    pol = probe.with_min_kid(floor)
    sch = CutRatioScheduler(T, samplers=_menu(), admission=pol)
    bumped = _req(0, 0.1)                        # nominal cut fails -> bump
    d = pol.decide(bumped)
    assert d.bumped
    assert sch.server_cost(bumped) == float(d.effective_cut) < nominal


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------
def _engine(world, pol=None, **kw):
    sched, server, _, _ = world
    kw.setdefault("slots", 4)
    kw.setdefault("samplers", _menu())
    cfg = EngineConfig(sched=sched, apply_fn=_apply_fn, image_shape=SHAPE,
                       admission=pol, **kw)
    return ServeEngine(cfg, server)


def test_engine_serves_only_above_floor_and_surfaces_decisions(world, probe):
    """The online guarantee: every SERVED request's disclosure KID (at its
    effective cut, bumped included) clears the floor; rejected requests
    have decisions but no completions; the summary counts agree."""
    sched, server, stack, _ = world
    prof = probe.profile("ddim")
    nominal = CutPlan(T, 0.1).cut_index(_menu()["ddim"])
    below = [p for p in range(nominal) if prof[p] > prof[nominal]]
    floor = (prof[nominal] + max(prof[p] for p in below)) / 2
    pol = probe.with_min_kid(floor)
    reqs = [_req(i, c, sampler=s) for i, (c, s) in enumerate(
        [(0.1, "ddim"), (0.5, "ddim"), (0.9, "ddim"),
         (0.1, "ddpm"), (0.5, "ddpm"), (0.9, "ddpm")])]
    eng = _engine(world, pol, scheduler=make_scheduler("cut_ratio", T,
                                                       samplers=_menu()))
    res = eng.serve(list(reqs), stack)
    assert set(res.decisions) == set(range(6))
    for rid, d in res.decisions.items():
        if d.served:
            assert rid in res.completions
            assert pol.disclosure_kid(d.sampler, d.effective_cut) >= floor
            assert d.kid >= floor
        else:
            assert rid not in res.completions
    adm = res.summary["admission"]
    acts = [d.action for d in res.decisions.values()]
    assert adm["admitted"] == acts.count("admit")
    assert adm["bumped"] == acts.count("bump") >= 1
    assert adm["rejected"] == acts.count("reject")
    assert res.summary["served"] == len(res.completions)
    if adm["admitted"] + adm["bumped"]:
        assert adm["disclosure_kid"]["min"] >= floor


def test_engine_bumped_request_matches_reference_at_effective_cut(world,
                                                                  probe):
    """A bumped request is genuinely served at the noisier cut: its lanes
    reproduce the split generation with the server segment stopping at the
    EFFECTIVE position and the client finishing from there."""
    sched, server, stack, _ = world
    prof = probe.profile("ddim")
    nominal = CutPlan(T, 0.1).cut_index(_menu()["ddim"])
    below = [p for p in range(nominal) if prof[p] > prof[nominal]]
    floor = (prof[nominal] + max(prof[p] for p in below)) / 2
    pol = probe.with_min_kid(floor)
    r = _req(0, 0.1, batch=2, client_idx=1)
    d = pol.decide(r)
    assert d.bumped
    res = _engine(world, pol).serve([r], stack)
    comp = res.completions[0]
    smp = _menu()["ddim"]
    server_fn = functools.partial(_apply_fn, server)
    client_fn = functools.partial(_apply_fn, adamw.tree_unstack(stack, 1))
    for i in range(r.batch):
        k_init, k_srv, k_cli = jax.random.split(
            jax.random.fold_in(r.key, i), 3)
        x_T = jax.random.normal(k_init, SHAPE, jnp.float32)
        mid = sample_trajectory(sched, smp, server_fn, k_srv, x_T[None],
                                0, d.effective_cut)[0]
        x0 = sample_trajectory(sched, smp, client_fn, k_cli, mid[None],
                               d.effective_cut, smp.K)[0]
        np.testing.assert_allclose(comp.x_mid[i], np.asarray(mid),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(comp.x0[i], np.asarray(x0),
                                   rtol=1e-5, atol=1e-5)


def test_engine_gate_off_and_clearing_gate_are_bitwise_ungated(world, probe):
    """admission=None and a gate every request clears produce bitwise
    identical completions: the gate changes nothing unless it binds."""
    sched, server, stack, _ = world
    reqs = lambda: [_req(i, c, sampler=s) for i, (c, s) in enumerate(
        [(0.25, "ddim"), (0.5, "ddpm"), (0.75, "ddim")])]
    res_off = _engine(world, None).serve(reqs(), stack)
    res_clear = _engine(world, probe.with_min_kid(float("-inf"))).serve(
        reqs(), stack)
    assert res_off.decisions == {}
    assert all(d.action == "admit" for d in res_clear.decisions.values())
    for rid in res_off.completions:
        np.testing.assert_array_equal(res_off.completions[rid].x_mid,
                                      res_clear.completions[rid].x_mid)
        np.testing.assert_array_equal(res_off.completions[rid].x0,
                                      res_clear.completions[rid].x0)


def test_engine_gate_deterministic_across_runs(world, probe):
    """Same traffic, same policy, two runs: identical decisions AND
    bitwise identical tensors (scores are cached floats; the engine path
    is seeded)."""
    sched, server, stack, _ = world
    prof = probe.profile("ddim")
    pol = probe.with_min_kid((min(prof) + max(prof)) / 2)
    reqs = lambda: [_req(i, (0.1, 0.5, 0.9)[i % 3]) for i in range(5)]
    eng = _engine(world, pol)
    r1 = eng.serve(reqs(), stack)
    r2 = eng.serve(reqs(), stack)
    assert r1.decisions == r2.decisions
    assert set(r1.completions) == set(r2.completions)
    for rid in r1.completions:
        np.testing.assert_array_equal(r1.completions[rid].x_mid,
                                      r2.completions[rid].x_mid)
        np.testing.assert_array_equal(r1.completions[rid].x0,
                                      r2.completions[rid].x0)


def test_engine_all_rejected_returns_empty(world, probe):
    sched, server, stack, _ = world
    floor = max(max(probe.profile("ddim")), max(probe.profile("ddpm"))) + 1.0
    pol = probe.with_min_kid(floor)
    res = _engine(world, pol).serve([_req(0, 0.2), _req(1, 0.8)], stack)
    assert res.completions == {}
    assert all(d.action == "reject" for d in res.decisions.values())
    assert res.summary["admission"]["rejected"] == 2
    assert res.summary["served"] == 0


def test_rebinding_changed_weights_bumps_version_and_rescores(world):
    """A weight swap must never leave stale disclosure scores gating the
    new model's tensors: binding a policy calibrated under one server
    model into an engine running DIFFERENT weights bumps
    ``params_version`` and drops every cached score and decision, so the
    next decide re-scores under the weights actually serving."""
    sched, server, _, calib = world
    other = _init_fn(jax.random.PRNGKey(99))
    pol = AdmissionPolicy(sched, calib, min_kid=0.0, samplers=_menu(),
                          server_fn=functools.partial(_apply_fn, other))
    stale_profile = pol.profile("ddim")
    stale_decision = pol.decide(_req(0, 0.5))
    assert pol._kid_cache and pol._decision_cache
    assert pol.params_version == 0
    _engine(world, pol)                      # binds the ENGINE's weights
    assert pol.params_version == 1
    assert not pol._kid_cache and not pol._decision_cache
    # re-scored under the engine's weights: a fresh policy built directly
    # against them must agree exactly (and the stale scores must not)
    ref = AdmissionPolicy(sched, calib, min_kid=0.0, samplers=_menu(),
                          server_fn=functools.partial(_apply_fn, server))
    assert pol.profile("ddim") == ref.profile("ddim")
    assert pol.profile("ddim") != stale_profile
    d = pol.decide(_req(0, 0.5))
    assert (d.kid, d.effective_cut) == \
        ((rd := ref.decide(_req(0, 0.5))).kid, rd.effective_cut)
    del stale_decision
    # same weights (even via a distinct partial object): NO bump
    ok = AdmissionPolicy(sched, calib, min_kid=0.0, samplers=_menu(),
                         server_fn=functools.partial(_apply_fn, server))
    ok.profile("ddim")
    cached = dict(ok._kid_cache)
    _engine(world, ok)
    assert ok.params_version == 0 and ok._kid_cache == cached


# ---------------------------------------------------------------------------
# satellite: engine <-> scheduler sampler-menu agreement
# ---------------------------------------------------------------------------
def test_engine_rejects_scheduler_with_divergent_menu(world):
    sched, server, _, _ = world
    other = {"ddpm": make_sampler(T),
             "ddim": make_sampler(T, "ddim", K + 1, eta=0.0)}  # different K
    sch = CutRatioScheduler(T, samplers=other)
    with pytest.raises(AssertionError, match="sampler 'ddim' differs"):
        _engine(world, None, scheduler=sch)
    missing = {"ddpm": make_sampler(T)}                        # missing name
    with pytest.raises(AssertionError, match="menus diverge"):
        _engine(world, None, scheduler=CutRatioScheduler(T, samplers=missing))


def test_assert_same_menu_passes_on_equal_menus():
    assert_same_menu(_menu(), _menu())
    eq = {"d": Sampler(make_sampler(T).trajectory, "ddim", 1.0)}
    assert_same_menu(eq, dict(eq))


# ---------------------------------------------------------------------------
# satellite: pow-2 padded finisher jit cache
# ---------------------------------------------------------------------------
def test_finisher_jit_cache_stable_under_width_churn(world):
    """Widths 3 and 4 land in the same pow-2 bucket: ONE finisher compile
    for both traffic mixes, and outputs still match the per-lane
    reference (padding lanes are masked out).  Every finish program runs
    over one client's row, so the cache holds only one-row shapes, even
    for a wave that spans three clients."""
    sched, server, stack, _ = world
    eng = _engine(world, None)
    jitted, shapes = eng._finish, []

    def recorded(client_stack, menu, x, *rest):
        shapes.append(tuple(x.shape[:2]))
        return jitted(client_stack, menu, x, *rest)
    eng._finish = recorded
    base = jitted._cache_size()
    r3 = _req(0, 0.5, sampler="ddpm", batch=3, client_idx=1)
    r4 = _req(1, 0.5, sampler="ddpm", batch=4, client_idx=1)
    res3 = eng.serve([r3], stack)
    assert jitted._cache_size() == base + 1
    res4 = eng.serve([r4], stack)
    assert jitted._cache_size() == base + 1   # width 3 and 4 -> pad 4
    eng.serve([_req(2, 0.5, sampler="ddpm", batch=3, client_idx=0),
               _req(3, 0.5, sampler="ddpm", batch=1, client_idx=1),
               _req(4, 0.5, sampler="ddpm", batch=4, client_idx=2)], stack)
    assert sorted(set(shapes)) == [(1, 2), (1, 4)]
    assert jitted._cache_size() == base + 2
    server_fn = functools.partial(_apply_fn, server)
    client_fn = functools.partial(_apply_fn, adamw.tree_unstack(stack, 1))
    for res, r in ((res3, r3), (res4, r4)):
        comp = res.completions[r.req_id]
        for i in range(r.batch):
            x0_ref = collafuse.split_sample_lane(
                sched, CutPlan(T, r.cut_ratio), server_fn, client_fn,
                jax.random.fold_in(r.key, i), SHAPE)
            np.testing.assert_allclose(comp.x0[i], np.asarray(x0_ref),
                                       rtol=1e-5, atol=1e-5)
