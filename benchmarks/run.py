"""Benchmark harness — one benchmark per paper table/figure + system benches.

Paper artefacts (CollaFuse, ECIS'24):
  fig1_disclosure   Fig. 1 — how concealed is x_t at each candidate cut step
                    (MSE + KID vs t), using the cosine schedule.
  fig3_tradeoff     Fig. 3 — cut-ratio sweep: KID performance (U-shape, H1),
                    disclosure at t_c (H2b), client FLOP share (H2c).
                    Short training budget so the full sweep runs on CPU.
  energy_split      H2c table — deterministic client/server FLOP accounting
                    per cut-ratio (codecarbon stand-in).

System benches:
  kernels           Pallas kernels (interpret mode) vs pure-jnp oracle:
                    correctness (max|Δ|) + per-call wall time.
  roofline          The §Roofline table, read from results/dryrun/*.json
                    (produced by ``python -m repro.launch.dryrun --sweep``).

Usage:
    PYTHONPATH=src python -m benchmarks.run                 # all (CPU-sized)
    PYTHONPATH=src python -m benchmarks.run --only fig3_tradeoff --rounds 120
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

# finisher_overlap measures TRUE client/server overlap, which needs the
# client segment on its own device queue (one CPU device runs XLA
# programs serially, so a multi-ms finish program head-of-line blocks
# every eager op behind it).  Two forced host devices model the paper's
# actual topology — client hardware separate from the server — and are
# inert for the single-device benches, which never leave device 0.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2").strip()

import jax
import jax.numpy as jnp

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")
DRYRUN = os.path.join(RESULTS, "dryrun")


def _timeit(fn, *args, warmup=1, iters=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6, out  # us/call


# ---------------------------------------------------------------------------
# Fig. 1 — concealment vs candidate cut step
# ---------------------------------------------------------------------------
def bench_fig1_disclosure(args):
    from repro.core import privacy
    from repro.data.synthetic import ClientDataConfig, make_client_datasets
    from repro.diffusion import ddpm
    from repro.diffusion.schedule import cosine_schedule

    T = 100
    sched = cosine_schedule(T)
    clients, _ = make_client_datasets(
        ClientDataConfig(n_clients=1, per_client=64, image_size=32))
    x0 = clients[0]
    fp = privacy.feature_params()
    key = jax.random.PRNGKey(0)
    print("# fig1_disclosure: concealment of x_t vs timestep t "
          "(cut c => t_split = c*T)")
    print("t,cut_ratio_equiv,mse,kid")
    rows = []
    for t_val in (5, 10, 20, 40, 60, 80, 95, 100):
        t = jnp.full((x0.shape[0],), t_val, jnp.int32)
        eps = jax.random.normal(jax.random.fold_in(key, t_val), x0.shape)
        x_t = ddpm.q_sample(sched, x0, t, eps)
        mse = float(privacy.mse_disclosure(x0, x_t))
        kid = float(privacy.kid(fp, x0, x_t))
        rows.append({"t": t_val, "c": t_val / T, "mse": mse, "kid": kid})
        print(f"{t_val},{t_val/T:.2f},{mse:.4f},{kid:.4f}")
    # paper claim: concealment grows with t — most steps hide the image
    mses = [r["mse"] for r in rows]
    assert all(a <= b + 1e-6 for a, b in zip(mses, mses[1:])), \
        "MSE concealment must be monotone in t"
    return rows


# ---------------------------------------------------------------------------
# Fig. 3 — the full trade-off sweep (reduced budget)
# ---------------------------------------------------------------------------
def bench_fig3_tradeoff(args):
    import dataclasses

    from repro.configs.base import UNetConfig
    from repro.core import privacy
    from repro.core.trainer import CollaFuseTrainer, TrainerConfig
    from repro.data.synthetic import ClientDataConfig, image_batches, \
        make_client_datasets
    from repro.models import unet

    ucfg = dataclasses.replace(
        UNetConfig().reduced(), image_size=16, base_channels=16)
    dcfg = ClientDataConfig(n_clients=3, per_client=96, image_size=16,
                            holdout=48)
    clients, holdout = make_client_datasets(dcfg)
    init_fn = functools.partial(unet.init_params, cfg=ucfg)
    apply_fn = lambda p, x, t: unet.forward(p, x, t, ucfg)
    fp = privacy.feature_params()

    print("# fig3_tradeoff: cut-ratio sweep "
          f"({args.rounds} rounds each, 16x16, T=50)")
    print("cut_ratio,kid_train_sum,kid_holdout_sum,"
          "disclosure_mse,client_flop_fraction")
    rows = []
    for c in (0.0, 0.2, 0.4, 0.6, 0.8, 1.0):
        tcfg = TrainerConfig(n_clients=3, T=50, cut_ratio=c, lr=1e-3)
        tr = CollaFuseTrainer(tcfg, init_fn, apply_fn)
        iters = [image_batches(cl, 32, seed=i)
                 for i, cl in enumerate(clients)]
        m = {}
        for _ in range(args.rounds):
            m = tr.train_round([next(it) for it in iters])
        kid_tr, kid_ho, mse_d = 0.0, 0.0, 0.0
        for k in range(3):
            key = jax.random.fold_in(jax.random.PRNGKey(7), k)
            gen = tr.sample(key, (32, 16, 16, 1), client_idx=k)
            disclosed = tr.disclosed(key, clients[k][:32], client_idx=k)
            kid_tr += float(privacy.kid(fp, clients[k], gen))
            kid_ho += float(privacy.kid(fp, holdout, gen))
            mse_d += float(privacy.mse_disclosure(clients[k][:32],
                                                  disclosed)) / 3
        rows.append({"c": c, "kid_train_sum": kid_tr,
                     "kid_holdout_sum": kid_ho, "disclosure_mse": mse_d,
                     "client_flops": m["client_fraction"]})
        print(f"{c:.1f},{kid_tr:+.4f},{kid_ho:+.4f},{mse_d:.4f},"
              f"{m['client_fraction']:.3f}", flush=True)
    # H2c invariant: client share of compute is monotone in c
    fr = [r["client_flops"] for r in rows]
    assert all(a <= b + 1e-9 for a, b in zip(fr, fr[1:])), fr
    with open(os.path.join(RESULTS, "bench_fig3.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return rows


# ---------------------------------------------------------------------------
# H2c — energy/FLOP split accounting
# ---------------------------------------------------------------------------
def bench_energy_split(args):
    from repro.core.collafuse import CutPlan, flops_split
    print("# energy_split: client/server denoising FLOPs per cut-ratio "
          "(T=100, 1 GFLOP/model-call, batch 150 — paper's setup)")
    print("cut_ratio,server_gflops,client_gflops,client_fraction")
    rows = []
    for c in (0.0, 0.2, 0.4, 0.6, 0.8, 1.0):
        s = flops_split(CutPlan(100, c), 1e9, 150)
        rows.append(s)
        print(f"{c:.1f},{s['server_flops']/1e9:.0f},"
              f"{s['client_flops']/1e9:.0f},{s['client_fraction']:.3f}")
    return rows


# ---------------------------------------------------------------------------
# Shared backbone for the orchestration benches
# ---------------------------------------------------------------------------
def _tiny_mlp_eps_model(size: int = 8, hidden: int = 64, tdim: int = 16):
    """Deliberately tiny matmul-only eps-model shared by clients_scaling
    and serve_continuous, so both measure ENGINE orchestration (dispatch,
    pooling, slot management) over the same backbone and stay comparable."""
    import numpy as np

    d = size * size

    def init_fn(key):
        ks = jax.random.split(key, 3)
        s = lambda k, shape, fan: jax.random.normal(k, shape) / np.sqrt(fan)
        return {"w1": s(ks[0], (d + tdim, hidden), d + tdim),
                "w2": s(ks[1], (hidden, hidden), hidden),
                "w3": s(ks[2], (hidden, d), hidden)}

    def apply_fn(p, x, t):
        b = x.shape[0]
        freqs = jnp.exp(jnp.linspace(0.0, 3.0, tdim // 2))
        ang = t[:, None].astype(jnp.float32) * freqs[None]
        temb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
        h = jnp.concatenate([x.reshape(b, -1), temb], -1)
        h = jax.nn.silu(h @ p["w1"])
        h = jax.nn.silu(h @ p["w2"])
        return (h @ p["w3"]).reshape(x.shape)

    return init_fn, apply_fn


# ---------------------------------------------------------------------------
# Multi-client round scaling — batched (vmap/pjit) engine vs looped baseline
# ---------------------------------------------------------------------------
def bench_clients_scaling(args):
    """Tentpole bench: round wall-time vs n_clients for the batched engine
    (ONE fused server round + ONE vmapped client round) against the looped
    per-client reference.  The backbone is a deliberately tiny MLP
    eps-model (matmuls only) so the measurement isolates ENGINE
    orchestration — per-client dispatch, host pooling, metric syncs — the
    regime the paper's resource-constrained clients live in.  (Conv
    backbones gain less from single-device vmap because XLA CPU lowers
    per-client-kernel convolutions to a serial loop; the mesh-sharded
    path in launch/clients_sweep.py is the lever there.)  Writes
    results/BENCH_clients_scaling.json so CI accumulates the perf
    trajectory.  ``--toy`` shrinks the sweep for the CI smoke job (and
    skips the speedup gate, which is calibrated for a full CPU run)."""
    from repro.core.trainer import CollaFuseTrainer, TrainerConfig

    sizes = (2, 4) if args.toy else (2, 8, 32, 64)
    rounds = 2 if args.toy else 5
    batch = 4
    size = 8
    init_fn, apply_fn = _tiny_mlp_eps_model(size)

    def timed(trainer, data):
        for _ in range(2):                          # compile + warmup
            m = trainer.train_round(data)
        samples = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            m = trainer.train_round(data)
            samples.append(time.perf_counter() - t0)
        return sorted(samples)[len(samples) // 2], m    # median round

    print(f"# clients_scaling: round wall-time vs n_clients "
          f"({size}x{size} MLP eps-model, T=20, batch {batch}, "
          f"{rounds} timed rounds)")
    print("n_clients,batched_s,looped_s,speedup,server_gflops,client_gflops")
    rows = []
    import dataclasses
    for n in sizes:
        ks = jax.random.split(jax.random.PRNGKey(0), n)
        data = [jax.random.normal(k, (batch, size, size, 1)) for k in ks]
        cfg = TrainerConfig(n_clients=n, T=20, cut_ratio=0.8)
        b_s, m = timed(CollaFuseTrainer(cfg, init_fn, apply_fn), data)
        l_s, _ = timed(CollaFuseTrainer(
            dataclasses.replace(cfg, batched=False), init_fn, apply_fn),
            data)
        rows.append({"n_clients": n, "batched_s": b_s, "looped_s": l_s,
                     "speedup": l_s / b_s,
                     "server_flops": m["server_flops"],
                     "client_flops": m["client_flops"]})
        print(f"{n},{b_s:.4f},{l_s:.4f},{l_s/b_s:.2f},"
              f"{m['server_flops']/1e9:.3f},{m['client_flops']/1e9:.3f}",
              flush=True)
    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "BENCH_clients_scaling.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {out}")
    if not args.toy:
        # batched round time must grow SUBLINEARLY in n_clients ...
        t0, tN = rows[0], rows[-1]
        growth = (tN["batched_s"] / t0["batched_s"])
        factor = tN["n_clients"] / t0["n_clients"]
        assert growth < factor, \
            f"batched round not sublinear: {growth:.1f}x time for " \
            f"{factor:.0f}x clients"
        # ... and beat the looped engine >=3x at n_clients=32 (issue gate)
        at32 = next(r for r in rows if r["n_clients"] == 32)
        assert at32["speedup"] >= 3.0, \
            f"batched engine only {at32['speedup']:.2f}x at 32 clients"
    return rows


# ---------------------------------------------------------------------------
# Continuous-batching serving engine vs sequential per-request split_sample
# ---------------------------------------------------------------------------
def bench_serve_continuous(args):
    """Tentpole serving bench: wall-time to serve a queue of generation
    requests (mixed cut-ratios, batch sizes, client models) through the
    continuous-batching engine (ONE masked denoise dispatch per tick,
    retire-at-t_split, vmapped client finisher) against the sequential
    per-request ``split_sample`` baseline.  The backbone is the same tiny
    MLP eps-model as clients_scaling so the measurement isolates ENGINE
    orchestration.  Gate (full run): ≥3x at 32 in-flight requests.  Writes
    results/BENCH_serve.json (uploaded by the CI serve_smoke job)."""
    import numpy as np

    from repro.core import collafuse
    from repro.core.collafuse import CutPlan
    from repro.diffusion.schedule import cosine_schedule
    from repro.optim import adamw
    from repro.serve import (EngineConfig, Request, ServeEngine,
                             make_scheduler, time_sequential)
    from repro.serve.engine import sequential_fns

    slots, n_requests, T = (8, 16, 10) if args.toy else (32, 64, 50)
    n_clients = 4
    size = 8
    shape = (size, size, 1)
    cut_ratios = (0.25, 0.5, 0.75)
    init_fn, apply_fn = _tiny_mlp_eps_model(size)

    sched = cosine_schedule(T)
    server_params = init_fn(jax.random.PRNGKey(0))
    client_stack = adamw.tree_stack(
        [init_fn(k) for k in jax.random.split(jax.random.PRNGKey(1),
                                              n_clients)])
    requests = [Request(req_id=i, key=jax.random.fold_in(
                            jax.random.PRNGKey(7), i),
                        batch=1, cut_ratio=cut_ratios[i % len(cut_ratios)],
                        client_idx=i % n_clients)
                for i in range(n_requests)]

    cfg = EngineConfig(sched=sched, apply_fn=apply_fn, image_shape=shape,
                       slots=slots, scheduler=make_scheduler("fifo", T))
    eng = ServeEngine(cfg, server_params)

    print(f"# serve_continuous: {n_requests} requests (batch 1, "
          f"c∈{cut_ratios}) on {slots} slots, T={T}, MLP eps-model")
    eng.serve(list(requests), client_stack)                # compile + warmup
    res = eng.serve(list(requests), client_stack)          # warm jit cache

    server_fn, client_fn_for = sequential_fns(apply_fn, server_params,
                                              client_stack)
    seq_s = time_sequential(cfg, requests, server_params, client_stack)

    # spot-check the engine against the per-lane sample_range reference
    for r in (requests[0], requests[-1]):
        comp = res.completions[r.req_id]
        ref_x0, ref_mid = collafuse.split_sample_lane(
            sched, CutPlan(T, r.cut_ratio), server_fn,
            client_fn_for(r.client_idx), jax.random.fold_in(r.key, 0),
            shape, return_intermediate=True)
        np.testing.assert_allclose(comp.x_mid[0], np.asarray(ref_mid),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(comp.x0[0], np.asarray(ref_x0),
                                   rtol=1e-5, atol=1e-5)

    speedup = seq_s / res.wall_s
    rec = {"scenario": "serve_continuous", "toy": bool(args.toy),
           "slots": slots, "n_requests": n_requests, "T": T,
           "cut_ratios": list(cut_ratios), "engine_s": res.wall_s,
           "sequential_s": seq_s, "speedup": speedup, **res.summary}
    print("engine_s,sequential_s,speedup,requests_per_s,"
          "latency_ticks_p50,latency_ticks_p95,utilization_mean")
    print(f"{res.wall_s:.3f},{seq_s:.3f},{speedup:.2f},"
          f"{res.summary['requests_per_s']:.1f},"
          f"{res.summary['latency_ticks_p50']:.0f},"
          f"{res.summary['latency_ticks_p95']:.0f},"
          f"{res.summary['utilization_mean']:.2f}", flush=True)
    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "BENCH_serve.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"# wrote {out}")
    if not args.toy:
        # issue gate: continuous batching >=3x sequential at 32 in-flight
        assert speedup >= 3.0, \
            f"continuous batching only {speedup:.2f}x over sequential"
    return rec


# ---------------------------------------------------------------------------
# Fused masked denoise-tick kernel: bytes-accessed gate + equivalence
# ---------------------------------------------------------------------------
def _pallas_call_bytes(f, *example_args, full_size: int) -> float:
    """Measured traffic of a fused path, from its traced jaxpr.

    Asserts the program really is ONE pallas_call (recursing through pjit/
    scan/cond sub-jaxprs) and that no OTHER primitive materializes a
    full-slot-array-sized tensor (``reshape`` views excepted) — so a
    regression that splits the select/clip into an extra jnp pass over the
    slot array, or adds a second kernel launch, FAILS the gate rather than
    sliding under a hand-written byte formula.  Returns the pallas_call's
    operand+result bytes (what one read of each input + one write costs).
    """
    calls, extras = [], []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            has_sub = False
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                        has_sub = True
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)
                        has_sub = True
            if eqn.primitive.name == "pallas_call":
                # the kernel is traced twice — compiled for a TPU and
                # interpreted elsewhere — and one of the two is lowered
                if not eqn.params["interpret"]:
                    calls.append(eqn)
            elif not has_sub and eqn.primitive.name != "reshape":
                # call-like eqns (pjit, scan, ...) are accounted by their
                # walked sub-jaxpr, not by their own result bindings
                extras.extend(ov for ov in eqn.outvars
                              if ov.aval.size >= full_size)

    walk(jax.make_jaxpr(f)(*example_args).jaxpr)
    assert len(calls) == 1, \
        f"fused path must be ONE pallas_call, traced {len(calls)}"
    assert not extras, \
        f"slot-array-sized tensors materialized outside the kernel: " \
        f"{[str(v.aval) for v in extras]}"
    eqn = calls[0]
    return float(sum(v.aval.size * v.aval.dtype.itemsize
                     for v in list(eqn.invars) + list(eqn.outvars)))


def bench_masked_step(args):
    """Bytes-accessed gate for the fused masked tick kernel (the serving
    engine's hot loop) against the jnp masked path.

    Byte accounting, per path:

    * jnp masked path: XLA ``cost_analysis()`` on the LOWERED (pre-fusion)
      HLO of ``p_sample_masked`` — operator-granularity accounting where
      every op in the gather→step→clip→where chain is one HBM round-trip
      of the slot array (the cost wherever producer/consumer fusion cannot
      collapse the chain).  The post-optimisation compiled number is also
      recorded for transparency (XLA CPU fuses the chain to near the
      streaming floor; the kernel makes that floor explicit and portable).
    * fused path: operand+result bytes of the single pallas_call MEASURED
      from the traced jaxpr (``_pallas_call_bytes`` — which also fails on
      a second kernel launch or an un-fused full-array pass), cross-checked
      against the kernel's advertised ``pl.CostEstimate``
      (``masked_step_bytes`` — what the XLA custom call reports on TPU).

    Gate: fused bytes must be ≥2x fewer.  Numerical equivalence is asserted
    per lane — active lanes vs the jnp reference, inactive lanes bitwise
    pass-through at out-of-range t, and the t==1 no-noise edge.  Writes
    results/BENCH_masked_step.json (uploaded by the CI kernels_smoke job).
    """
    import numpy as np

    from repro.diffusion import ddpm
    from repro.diffusion.schedule import cosine_schedule
    from repro.kernels.ddpm_step import masked_step_bytes

    slots, T = (8, 10) if args.toy else (64, 50)
    size = 16
    sched = cosine_schedule(T)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    shape = (slots, size, size, 1)
    x = jax.random.normal(ks[0], shape, jnp.float32)
    eps = jax.random.normal(ks[1], shape, jnp.float32)
    noise = jax.random.normal(ks[2], shape, jnp.float32)
    # heterogeneous per-lane t incl. idle-lane junk (0, negative, > T);
    # ~1/4 of the lanes inactive, at least one active lane pinned at t=1
    t = (jnp.arange(slots, dtype=jnp.int32) * 3) % (T + 4) - 2
    t = t.at[0].set(1)
    active = ((jnp.arange(slots) % 4) != 3).at[0].set(True)

    f_jnp = jax.jit(lambda x1, t1, e1, n1, a1: ddpm.p_sample_masked(
        sched, x1, t1, e1, n1, a1, backend="jnp"))
    f_fused = jax.jit(lambda x1, t1, e1, n1, a1: ddpm.p_sample_masked(
        sched, x1, t1, e1, n1, a1, backend="pallas_masked"))

    lowered = f_jnp.lower(x, t, eps, noise, active)
    ca_hlo = lowered.cost_analysis()
    ca_opt = lowered.compile().cost_analysis()
    ca_hlo = ca_hlo[0] if isinstance(ca_hlo, (list, tuple)) else ca_hlo
    ca_opt = ca_opt[0] if isinstance(ca_opt, (list, tuple)) else ca_opt
    bytes_jnp = float(ca_hlo["bytes accessed"])
    bytes_jnp_compiled = float(ca_opt["bytes accessed"])
    bytes_kernel = _pallas_call_bytes(f_fused, x, t, eps, noise, active,
                                      full_size=x.size)
    # the advertised CostEstimate must track the measured traffic (±1%) —
    # the TPU scheduler is told this number, so it may not drift
    bytes_advertised = float(masked_step_bytes(x, T))
    assert abs(bytes_advertised - bytes_kernel) <= 0.01 * bytes_kernel, \
        f"CostEstimate {bytes_advertised:.0f} drifted from measured " \
        f"pallas_call bytes {bytes_kernel:.0f}"
    ratio = bytes_jnp / bytes_kernel

    # ---- numerical equivalence, per lane ------------------------------
    out_ref = np.asarray(f_jnp(x, t, eps, noise, active))
    out_fused = np.asarray(f_fused(x, t, eps, noise, active))
    act = np.asarray(active)
    np.testing.assert_allclose(out_fused[act], out_ref[act],
                               rtol=1e-5, atol=1e-6,
                               err_msg="active lanes diverge")
    np.testing.assert_array_equal(out_fused[~act], np.asarray(x)[~act],
                                  err_msg="inactive lanes not bit-identical")
    # t==1 edge: lane 0 must ignore its noise draw entirely
    out_shift = np.asarray(f_fused(x, t, eps, noise + 100.0, active))
    np.testing.assert_array_equal(out_fused[0], out_shift[0],
                                  err_msg="t==1 lane depends on noise")

    us_jnp, _ = _timeit(f_jnp, x, t, eps, noise, active)
    us_fused, _ = _timeit(f_fused, x, t, eps, noise, active)

    print(f"# masked_step: {slots} lanes x {size}x{size}x1, T={T} "
          f"(fused kernel in "
          f"{'compiled' if jax.default_backend() == 'tpu' else 'interpret'}"
          f" mode — wall time only meaningful compiled)")
    print("path,bytes_accessed,us_per_call")
    print(f"jnp_masked_hlo,{bytes_jnp:.0f},{us_jnp:.0f}")
    print(f"jnp_masked_compiled,{bytes_jnp_compiled:.0f},{us_jnp:.0f}")
    print(f"pallas_masked_fused,{bytes_kernel:.0f},{us_fused:.0f}")
    print(f"bytes ratio (jnp chain / fused kernel): {ratio:.2f}x", flush=True)

    rec = {"scenario": "masked_step", "toy": bool(args.toy),
           "slots": slots, "image": size, "T": T,
           "bytes_jnp_hlo": bytes_jnp,
           "bytes_jnp_compiled": bytes_jnp_compiled,
           "bytes_fused_kernel": bytes_kernel,
           "bytes_ratio": ratio,
           "us_jnp": us_jnp, "us_fused": us_fused,
           "equivalence": "active allclose 1e-5; inactive bitwise; "
                          "t==1 noise-independent"}
    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "BENCH_masked_step.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"# wrote {out}")
    # issue gate (deterministic — holds at toy scale too): the fused tick
    # must cut >=2x the bytes of the unfused masked chain
    assert ratio >= 2.0, \
        f"fused masked kernel only {ratio:.2f}x fewer bytes than jnp chain"
    return rec


# ---------------------------------------------------------------------------
# Strided DDIM trajectories through the serving engine + sampler-refactor
# equivalence gates
# ---------------------------------------------------------------------------
def bench_ddim_speedup(args):
    """Sampler-layer bench: serving cost of strided DDIM trajectories vs
    the dense DDPM chain through the SAME continuous-batching engine
    (same slot capacity, same backbone), plus the refactor-safety
    equivalence of the trajectory machinery.

    Gates (both deterministic — they hold at toy scale too):

    * a DDIM-K request retires in >= 5x fewer server ticks than a dense
      DDPM request at the same cut-ratio — tick counts, not wall time, so
      the gate measures the step-budget multiplier, not CPU noise;
    * the dense-trajectory eta=1 sampler reproduces ``sample_range`` /
      ``split_sample`` per StepBackend (allclose; the jnp path BITWISE) —
      i.e. threading trajectories through five layers changed nothing for
      the dense chain.

    Writes results/BENCH_ddim.json (uploaded by the CI bench-smoke job).
    """
    import numpy as np

    from repro.core import collafuse
    from repro.core.collafuse import CutPlan
    from repro.diffusion import ddpm
    from repro.diffusion.sampler import (Sampler, dense_trajectory,
                                         make_sampler, sample_trajectory)
    from repro.diffusion.schedule import cosine_schedule
    from repro.serve import EngineConfig, Request, ServeEngine

    T, K = (200, 20) if args.toy else (1000, 50)
    slots, n_req = (8, 8) if args.toy else (32, 16)
    cut_ratio = 0.5
    size = 8
    shape = (size, size, 1)
    init_fn, apply_fn = _tiny_mlp_eps_model(size)

    sched = cosine_schedule(T)
    server_params = init_fn(jax.random.PRNGKey(0))
    samplers = {"ddpm": make_sampler(T),
                "ddim": make_sampler(T, "ddim", K, eta=0.0)}
    eng = ServeEngine(EngineConfig(sched=sched, apply_fn=apply_fn,
                                   image_shape=shape, slots=slots,
                                   samplers=samplers), server_params)

    def reqs(name):
        return [Request(req_id=i, key=jax.random.fold_in(
                            jax.random.PRNGKey(7), i),
                        batch=1, cut_ratio=cut_ratio, sampler=name)
                for i in range(n_req)]

    print(f"# ddim_speedup: {n_req} requests (c={cut_ratio}) on {slots} "
          f"slots — dense DDPM T={T} vs strided DDIM K={K}, same engine")
    rows = {}
    for name in ("ddpm", "ddim"):
        eng.serve(reqs(name))                         # compile + warmup
        res = eng.serve(reqs(name))
        rows[name] = {"ticks": res.summary["ticks"],
                      "ticks_per_request": res.summary["ticks"] / n_req,
                      "engine_s": res.wall_s,
                      "server_flops": res.summary["server_flops"]}
    ratio = (rows["ddpm"]["ticks_per_request"] /
             rows["ddim"]["ticks_per_request"])
    print("sampler,ticks,ticks_per_request,engine_s")
    for name, r in rows.items():
        print(f"{name},{r['ticks']},{r['ticks_per_request']:.2f},"
              f"{r['engine_s']:.3f}")
    print(f"server ticks per retired request (dense/ddim): {ratio:.2f}x",
          flush=True)

    # ---- refactor-safety: dense trajectory == legacy samplers ---------
    T_eq = 30
    sched_eq = cosine_schedule(T_eq)
    plan_eq = CutPlan(T_eq, 0.4)
    srv_eq = functools.partial(apply_fn, init_fn(jax.random.PRNGKey(3)))
    cli_eq = functools.partial(apply_fn, init_fn(jax.random.PRNGKey(4)))
    key = jax.random.PRNGKey(11)
    x_T = jax.random.normal(key, (4,) + shape, jnp.float32)
    dense_samplers = [make_sampler(T_eq),                   # ddpm family
                      Sampler(dense_trajectory(T_eq), "ddim", 1.0)]
    for backend in ("jnp", "pallas", "pallas_masked"):
        ref = ddpm.sample_range(sched_eq, srv_eq, key, x_T, T_eq, 1,
                                backend=backend)
        s_ref = collafuse.split_sample(sched_eq, plan_eq, srv_eq, cli_eq,
                                       key, (4,) + shape, backend=backend)
        for smp in dense_samplers:
            out = sample_trajectory(sched_eq, smp, srv_eq, key, x_T,
                                    backend=backend)
            s_out = collafuse.split_sample(sched_eq, plan_eq, srv_eq,
                                           cli_eq, key, (4,) + shape,
                                           backend=backend, sampler=smp)
            if backend == "jnp":
                np.testing.assert_array_equal(
                    np.asarray(out), np.asarray(ref),
                    err_msg=f"{smp.describe()} not bitwise sample_range")
                np.testing.assert_array_equal(
                    np.asarray(s_out), np.asarray(s_ref),
                    err_msg=f"{smp.describe()} not bitwise split_sample")
            else:
                np.testing.assert_allclose(
                    np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5,
                    err_msg=f"{smp.describe()} vs sample_range [{backend}]")
                np.testing.assert_allclose(
                    np.asarray(s_out), np.asarray(s_ref), rtol=1e-5,
                    atol=1e-5,
                    err_msg=f"{smp.describe()} vs split_sample [{backend}]")
    print("equivalence: dense eta=1 sampler == sample_range/split_sample "
          "per backend (jnp bitwise) OK")

    rec = {"scenario": "ddim_speedup", "toy": bool(args.toy),
           "slots": slots, "n_requests": n_req, "T": T, "K": K,
           "cut_ratio": cut_ratio, "dense": rows["ddpm"],
           "ddim": rows["ddim"], "ticks_ratio": ratio,
           "equivalence": "dense-trajectory eta=1 == sample_range/"
                          "split_sample per backend; jnp bitwise"}
    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "BENCH_ddim.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"# wrote {out}")
    # issue gate (deterministic tick counts — enforced at toy scale too)
    assert ratio >= 5.0, \
        f"DDIM-{K} only {ratio:.2f}x fewer server ticks per request " \
        f"than dense T={T}"
    return rec


# ---------------------------------------------------------------------------
# KID-gated admission: the privacy gate as an online serving guarantee
# ---------------------------------------------------------------------------
def bench_privacy_admission(args):
    """Privacy-admission bench: the disclosure-KID gate on mixed DDPM/DDIM
    traffic through the serving engine.

    The floor is derived from the MEASURED disclosure landscape (all
    seeded, so every number here is deterministic and the gates also run
    at toy scale in CI): ``min_kid`` is placed strictly between the
    weakest nominal cut's KID and the smallest clearable prefix maximum,
    so at least one request must BUMP to a noisier cut and every request
    can still be served.  Gates:

    * every SERVED request's disclosure KID (bumped included) >= min_kid;
    * total engine ticks gated <= 1.5x ungated on the same traffic (bumps
      only shorten the server segment, so the gate never costs serving
      throughput);
    * gate OFF == gate CLEARING: ``admission=None`` and an all-clearing
      floor produce bitwise identical tensors (the gate is a no-op until
      it binds — the pre-gate engine path is unchanged);
    * determinism: two gated runs agree bitwise, decisions included;
    * reject path: a floor above the whole landscape rejects everything.

    Writes results/BENCH_privacy.json (uploaded by the CI bench-smoke
    job, rendered by ``benchmarks.report``).
    """
    import numpy as np

    from repro.data.synthetic import ClientDataConfig, make_client_datasets
    from repro.diffusion.sampler import make_sampler
    from repro.diffusion.schedule import cosine_schedule
    from repro.serve import (AdmissionPolicy, EngineConfig, Request,
                             ServeEngine, make_scheduler)

    T, K = (20, 6) if args.toy else (50, 10)
    slots, n_req = (4, 9) if args.toy else (16, 24)
    calib_n = 8 if args.toy else 16
    size = 8
    shape = (size, size, 1)
    cut_ratios = (0.1, 0.4, 0.7)
    init_fn, apply_fn = _tiny_mlp_eps_model(size)

    sched = cosine_schedule(T)
    server_params = init_fn(jax.random.PRNGKey(0))
    server_fn = functools.partial(apply_fn, server_params)
    samplers = {"ddpm": make_sampler(T),
                "ddim": make_sampler(T, "ddim", K, eta=0.0)}
    calib_sets, _ = make_client_datasets(ClientDataConfig(
        n_clients=1, per_client=calib_n, image_size=size, holdout=2))
    calib = calib_sets[0]

    def requests():
        return [Request(req_id=i, key=jax.random.fold_in(
                            jax.random.PRNGKey(7), i),
                        batch=1, cut_ratio=cut_ratios[i % len(cut_ratios)],
                        sampler=("ddpm", "ddim")[i % 2])
                for i in range(n_req)]

    def engine(admission):
        cfg = EngineConfig(sched=sched, apply_fn=apply_fn, image_shape=shape,
                           slots=slots, samplers=samplers,
                           scheduler=make_scheduler("cut_ratio", T,
                                                    samplers=samplers),
                           admission=admission)
        return ServeEngine(cfg, server_params)

    # ---- measure the disclosure landscape, derive the floor -----------
    probe = AdmissionPolicy(sched, calib, min_kid=float("-inf"),
                            samplers=samplers, server_fn=server_fn)
    combos = sorted({(r.sampler, r.cut_ratio) for r in requests()})
    from repro.core.collafuse import CutPlan
    nominal_kids, prefix_maxes = [], []
    profiles = {}
    for name, c in combos:
        nom = CutPlan(T, c).cut_index(samplers[name])
        prof = probe.profile(name, max_pos=nom)
        profiles[f"{name}@c={c}"] = [round(v, 6) for v in prof]
        nominal_kids.append(prof[nom])
        prefix_maxes.append(max(prof))
    # strictly between the weakest nominal and the smallest clearable
    # prefix max: every combo can clear somewhere (no rejects), and the
    # weakest combo cannot clear at its nominal (>= 1 bump) — assert the
    # placement is possible before asserting its consequences
    lo, hi = min(nominal_kids), min(prefix_maxes)
    assert lo < hi, \
        f"landscape degenerate (min nominal {lo} !< min prefix-max {hi}):" \
        f" retune T/K/cut_ratios"
    min_kid = 0.5 * (lo + hi)

    print(f"# privacy_admission: {n_req} requests (c∈{cut_ratios}, "
          f"ddpm T={T} / ddim K={K} alternating) on {slots} slots, "
          f"calib={calib_n}, derived min_kid={min_kid:.5f}")

    # ---- ungated vs gate-clearing: bitwise no-op ----------------------
    res_off = engine(None).serve(requests())
    res_clear = engine(probe.with_min_kid(float("-inf"))).serve(requests())
    for rid in res_off.completions:
        np.testing.assert_array_equal(
            res_off.completions[rid].x_mid, res_clear.completions[rid].x_mid,
            err_msg=f"req {rid}: a clearing gate changed the engine")
    assert all(d.action == "admit" for d in res_clear.decisions.values())

    # ---- gated run: floor guarantee + tick budget + determinism -------
    gate = probe.with_min_kid(min_kid)
    res_g = engine(gate).serve(requests())
    # the second run gets a FULLY FRESH policy (fresh jit + score +
    # decision caches), so the determinism assert exercises real
    # re-scoring, not cached objects compared to themselves
    gate2 = AdmissionPolicy(sched, calib, min_kid=min_kid,
                            samplers=samplers, server_fn=server_fn)
    res_g2 = engine(gate2).serve(requests())
    assert res_g.decisions == res_g2.decisions, "gated decisions drifted"
    for rid in res_g.completions:
        np.testing.assert_array_equal(
            res_g.completions[rid].x_mid, res_g2.completions[rid].x_mid,
            err_msg=f"req {rid}: gated run not deterministic")
    adm = res_g.summary["admission"]
    assert adm["rejected"] == 0, \
        f"floor was placed below every prefix max, yet {adm['rejected']} " \
        f"requests were rejected"
    assert adm["bumped"] >= 1, "floor above the weakest nominal must bump"
    for d in res_g.decisions.values():
        assert d.served and d.kid >= min_kid
        assert gate.disclosure_kid(d.sampler, d.effective_cut) >= min_kid
    ticks_off, ticks_g = res_off.summary["ticks"], res_g.summary["ticks"]
    assert ticks_g <= 1.5 * ticks_off, \
        f"gated run cost {ticks_g} ticks vs {ticks_off} ungated (> 1.5x)"

    # ---- reject path: floor above the whole landscape -----------------
    reject_floor = max(max(p) for p in profiles.values()) + 1.0
    res_r = engine(probe.with_min_kid(reject_floor)).serve(requests())
    assert res_r.completions == {}
    assert res_r.summary["admission"]["rejected"] == n_req

    print("policy,ticks,served,admitted,bumped,rejected,"
          "kid_min_served,kid_mean_served")
    dk = adm.get("disclosure_kid", {})
    print(f"ungated,{ticks_off},{res_off.summary['requests']},-,-,-,-,-")
    print(f"gated,{ticks_g},{res_g.summary['served']},{adm['admitted']},"
          f"{adm['bumped']},{adm['rejected']},{dk.get('min', 0):.5f},"
          f"{dk.get('mean', 0):.5f}")
    print(f"tick ratio gated/ungated: {ticks_g / max(ticks_off, 1):.3f} "
          f"(gate: <= 1.5; bumps only shorten the server segment)",
          flush=True)

    rec = {"scenario": "privacy_admission", "toy": bool(args.toy),
           "slots": slots, "n_requests": n_req, "T": T, "K": K,
           "cut_ratios": list(cut_ratios), "calib": calib_n,
           "min_kid": min_kid, "profiles": profiles,
           "ticks_ungated": ticks_off, "ticks_gated": ticks_g,
           "ticks_ratio": ticks_g / max(ticks_off, 1),
           "admission": adm,
           "equivalence": "gate off == clearing gate bitwise; gated run "
                          "deterministic; reject floor empties the engine"}
    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "BENCH_privacy.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"# wrote {out}")
    return rec


# ---------------------------------------------------------------------------
# Pallas kernels vs oracle
# ---------------------------------------------------------------------------
def bench_pod_ticks(args):
    """k-tick scan-dispatch gate: the k=8 double-buffered engine must be
    BITWISE-equal to the k=1 synchronous engine on every completion —
    admission gate on AND off — and (full run) >=2x ticks/sec with 256
    in-flight requests churning through 32 slots.  The backbone is the
    tiny MLP eps-model so the measurement isolates dispatch/boundary
    overhead: k fuses k denoise ticks into one device call under
    lax.scan, async_depth=2 double-buffers the host loop, and
    retire/refill bookkeeping collapses from every tick to every k-th
    tick — the dominant cost under heavy slot churn.  Writes
    results/BENCH_pod_ticks.json (uploaded by the CI bench-smoke job)."""
    import dataclasses

    import numpy as np

    from repro.diffusion.sampler import make_sampler
    from repro.diffusion.schedule import cosine_schedule
    from repro.serve import (AdmissionPolicy, EngineConfig, Request,
                             ServeEngine)

    T, K = (10, 5) if args.toy else (50, 10)
    slots = 8 if args.toy else 32
    n_req = 12 if args.toy else 256
    k_hot, depth = 8, 2
    size = 8
    shape = (size, size, 1)
    cut_ratios = (0.25, 0.5, 0.75)
    init_fn, apply_fn = _tiny_mlp_eps_model(size)

    sched = cosine_schedule(T)
    server_params = init_fn(jax.random.PRNGKey(0))
    samplers = {"ddpm": make_sampler(T),
                "ddim": make_sampler(T, "ddim", K, eta=0.0)}

    def requests():
        return [Request(req_id=i, key=jax.random.fold_in(
                            jax.random.PRNGKey(7), i),
                        batch=1, cut_ratio=cut_ratios[i % len(cut_ratios)],
                        sampler=("ddpm", "ddim")[i % 2])
                for i in range(n_req)]

    def admission():
        # median floor over the ddim disclosure profile: some requests
        # bump, and the decisions must replay identically at every k
        calib = jnp.tanh(jax.random.normal(jax.random.PRNGKey(5),
                                           (8,) + shape))
        probe = AdmissionPolicy(sched, calib, min_kid=float("-inf"),
                                samplers=samplers,
                                server_fn=functools.partial(apply_fn,
                                                            server_params))
        return probe.with_min_kid(float(np.median(probe.profile("ddim"))))

    base_cfg = EngineConfig(sched=sched, apply_fn=apply_fn,
                            image_shape=shape, slots=slots,
                            samplers=samplers)

    def run(cfg, admit):
        eng = ServeEngine(dataclasses.replace(
            cfg, admission=admission() if admit else None), server_params)
        eng.serve(requests())                         # compile + warmup
        return eng.serve(requests())

    print(f"# pod_ticks: {n_req} in-flight (batch 1, mixed ddpm/ddim) on "
          f"{slots} slots, T={T} — k=1 sync vs k={k_hot} depth={depth}")
    print("admission,config,ticks,wall_s,ticks_per_s")
    rec = {"scenario": "pod_ticks", "toy": bool(args.toy), "slots": slots,
           "n_requests": n_req, "T": T, "k": k_hot, "async_depth": depth,
           "modes": {}}
    ratios = {}
    for admit in (False, True):
        base = run(base_cfg, admit)
        hot = run(dataclasses.replace(base_cfg, ticks_per_dispatch=k_hot,
                                      async_depth=depth), admit)
        assert set(hot.completions) == set(base.completions)
        assert hot.decisions == base.decisions
        for rid, comp in base.completions.items():
            np.testing.assert_array_equal(
                hot.completions[rid].x_mid, comp.x_mid,
                err_msg=f"req {rid} admission={admit}")
        label = "on" if admit else "off"
        for nm, res in (("k1", base), (f"k{k_hot}", hot)):
            print(f"{label},{nm},{res.summary['ticks']},{res.wall_s:.3f},"
                  f"{res.summary['ticks_per_s']:.1f}")
        ratios[label] = (hot.summary["ticks_per_s"] /
                         base.summary["ticks_per_s"])
        rec["modes"][f"admission_{label}"] = {
            "bitwise_equal": True,
            "base_ticks": base.summary["ticks"],
            "hot_ticks": hot.summary["ticks"],
            "base_wall_s": base.wall_s, "hot_wall_s": hot.wall_s,
            "base_ticks_per_s": base.summary["ticks_per_s"],
            "hot_ticks_per_s": hot.summary["ticks_per_s"],
            "ticks_per_s_ratio": ratios[label],
            "boundary_lag_p100": hot.summary.get("boundary_lag_p100", 0)}
        print(f"admission {label}: bitwise equal, "
              f"ticks/sec {ratios[label]:.2f}x", flush=True)
    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "BENCH_pod_ticks.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"# wrote {out}")
    if not args.toy:
        # issue gate: k-tick scan dispatch >=2x ticks/sec at 256 in-flight
        assert min(ratios.values()) >= 2.0, \
            f"k={k_hot} scan dispatch only {min(ratios.values()):.2f}x"
    return rec


def bench_hetero_packing(args):
    """Heterogeneous-traffic gate: trajectory-aware wave packing
    (``pack=True``) + spare-column dynamic sampler menus.  A mixed
    workload — dense DDPM, DDIM-25 and DDIM-10 trajectories across
    several cuts, batch sizes 1/4/8 interleaved so big dense heads block
    ragged frees — runs through the k-tick engine twice:

    * packing OFF vs ON must be BITWISE-equal per request (packing moves
      admission ticks, never numerics);
    * (full run) packing ON drains the same workload in >= 1.3x fewer
      engine ticks — the unpacked run leaks its freed slots to
      head-of-line blocking, measured as ``fragmentation_frac``;
    * registering an AD-HOC sampler between serves adds ZERO compiles of
      the masked-step scan program (``_tick`` jit cache-size assert): the
      trajectory menu is traced data in preallocated spare columns, not a
      closure constant.

    Writes results/BENCH_hetero.json (rendered by ``benchmarks.report
    --all``; uploaded by the CI bench-smoke job)."""
    import dataclasses

    import numpy as np

    from repro.diffusion.sampler import make_sampler
    from repro.diffusion.schedule import cosine_schedule
    from repro.serve import EngineConfig, FIFOScheduler, Request, ServeEngine

    T = 12 if args.toy else 50
    slots = 8 if args.toy else 32
    n_req = 48 if args.toy else 256
    k_hot, depth = 5, 2
    size = 8
    shape = (size, size, 1)
    init_fn, apply_fn = _tiny_mlp_eps_model(size)

    sched = cosine_schedule(T)
    server_params = init_fn(jax.random.PRNGKey(0))
    k_fine, k_coarse = (6, 4) if args.toy else (25, 10)
    statics = {"ddpm": make_sampler(T),
               f"ddim{k_fine}": make_sampler(T, "ddim", k_fine, eta=0.0),
               f"ddim{k_coarse}": make_sampler(T, "ddim", k_coarse,
                                               eta=0.0)}
    k_dyn = 3 if args.toy else 7
    dyn_name = f"ddim{k_dyn}"
    dyn = make_sampler(T, "ddim", k_dyn, eta=0.0)

    filler_classes = [(f"ddim{k_fine}", 0.2), (f"ddim{k_coarse}", 0.8),
                      (f"ddim{k_fine}", 0.8), (dyn_name, 0.5)]
    head_batch = slots

    def requests(salt):
        # every 3rd request is a BIG dense head (batch = the whole pool,
        # 80% of the chain); between them, batch-1 fillers whose class
        # ROTATES per request, so the unpacked FIFO walk runs maximally
        # mixed cohorts whose ragged frees strand behind each blocked
        # head — packing coalesces the fillers into same-class waves and
        # back-fills the budget the heads cannot use yet
        reqs, filler_i = [], 0
        for i in range(n_req):
            if i % 3 == 2:
                sampler, cut, batch = "ddpm", 0.2, head_batch
            else:
                sampler, cut = filler_classes[filler_i
                                              % len(filler_classes)]
                batch, filler_i = 1, filler_i + 1
            reqs.append(Request(
                req_id=i, key=jax.random.fold_in(
                    jax.random.PRNGKey(salt), i),
                batch=batch, cut_ratio=cut, sampler=sampler))
        return reqs

    base_cfg = EngineConfig(sched=sched, apply_fn=apply_fn,
                            image_shape=shape, slots=slots,
                            samplers=statics, spare_columns=k_dyn + 1,
                            ticks_per_dispatch=k_hot, async_depth=depth)

    def run(pack):
        eng = ServeEngine(dataclasses.replace(
            base_cfg, scheduler=FIFOScheduler(pack=pack)), server_params)
        eng.register_sampler(dyn_name, dyn)
        eng.serve(requests(3))                      # compile + warmup
        n_compiled = eng._tick._cache_size()
        # ad-hoc re-registration at the serve boundary: the measured run
        # prices/serves the fresh menu with ZERO new scan compiles
        eng.register_sampler(dyn_name, make_sampler(T, "ddim", k_dyn,
                                                    eta=0.0))
        res = eng.serve(requests(7))
        assert eng._tick._cache_size() == n_compiled, \
            "dynamic sampler registration recompiled the scan program"
        return res

    print(f"# hetero_packing: {n_req} requests (batch-1 fillers + "
          f"batch-{head_batch} dense heads; ddpm + ddim{k_fine}/"
          f"ddim{k_coarse}/{dyn_name} across cuts) on {slots} slots, "
          f"T={T}, k={k_hot} depth={depth}")
    print("packing,ticks,wall_s,fragmentation_frac")
    res_off = run(pack=False)
    res_on = run(pack=True)
    assert set(res_on.completions) == set(res_off.completions)
    for rid, comp in res_off.completions.items():
        np.testing.assert_array_equal(res_on.completions[rid].x_mid,
                                      comp.x_mid, err_msg=f"req {rid}")
    ratio = res_off.summary["ticks"] / max(res_on.summary["ticks"], 1)
    for label, res in (("off", res_off), ("on", res_on)):
        print(f"{label},{res.summary['ticks']},{res.wall_s:.3f},"
              f"{res.summary['fragmentation_frac']:.4f}")
    print(f"packing: bitwise equal, ticks-to-drain {ratio:.2f}x, "
          f"0 new compiles", flush=True)
    rec = {"scenario": "hetero_packing", "toy": bool(args.toy),
           "slots": slots, "n_requests": n_req, "T": T, "k": k_hot,
           "async_depth": depth,
           "samplers": sorted(statics) + [dyn_name],
           "bitwise_equal": True, "dynamic_menu_new_compiles": 0,
           "ticks_off": res_off.summary["ticks"],
           "ticks_on": res_on.summary["ticks"],
           "ticks_to_drain_ratio": ratio,
           "wall_s_off": res_off.wall_s, "wall_s_on": res_on.wall_s,
           "fragmentation_frac_off":
               res_off.summary["fragmentation_frac"],
           "fragmentation_frac_on": res_on.summary["fragmentation_frac"],
           "occupancy_by_class_on":
               res_on.summary["occupancy_by_class"]}
    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "BENCH_hetero.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"# wrote {out}")
    if not args.toy:
        # issue gate: step-homogeneous waves drain the mixed workload in
        # >= 1.3x fewer ticks than the head-of-line-blocked unpacked walk
        assert ratio >= 1.3, \
            f"wave packing only {ratio:.2f}x ticks-to-drain"
        assert (res_on.summary["fragmentation_frac"] <=
                res_off.summary["fragmentation_frac"]), "packing raised " \
            "fragmentation: free slots while demand waits"
    return rec


def bench_cfg_guidance(args):
    """Classifier-free-guidance serving gate: guided requests ride the
    SAME fused masked-step scan as unguided traffic, on doubled
    cond+uncond lane pairs blended before the step.  Gates:

    * w=0 anchor: rerouting a workload through guided w=0 menu twins
      (doubled lanes, guided step, ε̂-combine) leaves completions AND
      admission decisions (action, effective cut, KID) bitwise/exactly
      unchanged — the guided machinery is a numerical no-op at w=0;
    * one program: a mixed guided+unguided workload (256 requests at
      full scale) adds ZERO new ``_tick`` scan compiles after warmup —
      guidance lives in the traced coefficient table row and the pair/
      cond slot state, never in a new executable;
    * throughput: guided traffic sustains >= 0.45x the unguided
      ticks/sec at equal in-flight (full run only; the ideal is 0.5x —
      each image burns two lanes through one dispatch — and the margin
      absorbs pairing overhead);
    * privacy: every SERVED guided request's disclosure KID clears the
      floor, scored on the GUIDED trajectory (cache keyed (sampler,
      pos, w)), and two independently-built gates agree exactly.

    Writes results/BENCH_cfg.json (rendered by ``benchmarks.report
    --all``; uploaded by the CI bench-smoke job)."""
    import numpy as np

    from repro.core.collafuse import CutPlan
    from repro.data.synthetic import ClientDataConfig, make_client_datasets
    from repro.diffusion.sampler import make_sampler
    from repro.diffusion.schedule import cosine_schedule
    from repro.serve import (AdmissionPolicy, EngineConfig, Request,
                             ServeEngine)

    T, K = (12, 4) if args.toy else (50, 10)
    slots = 8 if args.toy else 32
    n_mix = 48 if args.toy else 256
    n_anchor = 12 if args.toy else 24
    n_thr = 16 if args.toy else 64
    calib_n = 8 if args.toy else 16
    size = 8
    shape = (size, size, 1)
    cuts = (0.25, 0.75)
    NC, tdim, hidden = 4, 16, 64
    d = size * size

    # conditional twin of _tiny_mlp_eps_model: a label embedding row per
    # class + a null row (index NC) added to the time embedding
    def init_fn(key):
        ks = jax.random.split(key, 4)
        s = lambda k, sh, fan: jax.random.normal(k, sh) / np.sqrt(fan)
        return {"w1": s(ks[0], (d + tdim, hidden), d + tdim),
                "w2": s(ks[1], (hidden, hidden), hidden),
                "w3": s(ks[2], (hidden, d), hidden),
                "yemb": s(ks[3], (NC + 1, tdim), tdim)}

    def apply_fn(p, x, t, y=None):
        b = x.shape[0]
        freqs = jnp.exp(jnp.linspace(0.0, 3.0, tdim // 2))
        ang = t[:, None].astype(jnp.float32) * freqs[None]
        temb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
        yc = (jnp.full((b,), NC, jnp.int32) if y is None
              else jnp.clip(y, 0, NC))
        temb = temb + p["yemb"][yc]
        h = jnp.concatenate([x.reshape(b, -1), temb], -1)
        h = jax.nn.silu(h @ p["w1"])
        h = jax.nn.silu(h @ p["w2"])
        return (h @ p["w3"]).reshape(x.shape)

    sched = cosine_schedule(T)
    server_params = init_fn(jax.random.PRNGKey(0))
    samplers = {
        "ddpm": make_sampler(T),
        "ddim": make_sampler(T, "ddim", K, eta=0.0),
        # w=0 twins walk the identical trajectories — the anchor pair
        "ddpm_g0": make_sampler(T, guidance=0.0),
        "ddim_g0": make_sampler(T, "ddim", K, eta=0.0, guidance=0.0),
        # real guidance scales for the mixed + throughput phases
        "ddpm_g": make_sampler(T, guidance=1.5),
        "ddim_g": make_sampler(T, "ddim", K, eta=0.0, guidance=2.0),
    }
    calib_sets, _ = make_client_datasets(ClientDataConfig(
        n_clients=1, per_client=calib_n, image_size=size, holdout=2))
    calib = calib_sets[0]

    def engine(admission):
        cfg = EngineConfig(sched=sched, apply_fn=apply_fn,
                           image_shape=shape, slots=slots,
                           samplers=samplers, admission=admission,
                           num_classes=NC)
        return ServeEngine(cfg, server_params)

    def reqs(names, n, salt, batch_of=lambda i: 1 + i % 2, cut=None):
        return [Request(req_id=i,
                        key=jax.random.fold_in(jax.random.PRNGKey(salt), i),
                        batch=batch_of(i),
                        cut_ratio=cut if cut else cuts[i % len(cuts)],
                        sampler=names[i % len(names)], label=i % NC)
                for i in range(n)]

    # ---- derive the floor from the measured (guided) landscape --------
    probe = AdmissionPolicy(sched, calib, min_kid=float("-inf"),
                            samplers=samplers)
    engine(probe)                        # binds uncond + cond server fns
    combos = [(nm, c) for nm in samplers for c in cuts] \
        + [("ddpm", 0.5), ("ddpm_g", 0.5)]
    nominal_kids, prefix_maxes, profiles = [], [], {}
    for nm in samplers:
        prof = probe.profile(nm, max_pos=max(
            CutPlan(T, c).cut_index(samplers[nm])
            for n2, c in combos if n2 == nm))
        profiles[nm] = [round(v, 6) for v in prof]
    for nm, c in combos:
        nom = CutPlan(T, c).cut_index(samplers[nm])
        nominal_kids.append(profiles[nm][nom])
        prefix_maxes.append(max(profiles[nm][:nom + 1]))
    # the w=0 twins must land on the EXACT unguided landscape
    assert profiles["ddpm_g0"] == profiles["ddpm"][:len(
        profiles["ddpm_g0"])], "w=0 guided KID profile diverged from ddpm"
    assert profiles["ddim_g0"] == profiles["ddim"][:len(
        profiles["ddim_g0"])], "w=0 guided KID profile diverged from ddim"
    lo, hi = min(nominal_kids), min(prefix_maxes)
    min_kid = 0.5 * (lo + hi) if lo < hi else lo
    print(f"# cfg_guidance: slots={slots} T={T} K={K} classes={NC} "
          f"cuts={cuts} min_kid={min_kid:.5f} "
          f"(landscape lo={lo:.5f} hi={hi:.5f})")

    gate = probe.with_min_kid(min_kid)
    eng = engine(gate)

    # ---- w=0 anchor: guided twins are a bitwise no-op -----------------
    plain_names, twin_names = ["ddpm", "ddim"], ["ddpm_g0", "ddim_g0"]
    res_a = eng.serve(reqs(plain_names, n_anchor, salt=3))
    res_b = eng.serve(reqs(twin_names, n_anchor, salt=3))
    assert set(res_a.completions) == set(res_b.completions)
    for rid, comp in res_a.completions.items():
        np.testing.assert_array_equal(
            res_b.completions[rid].x_mid, comp.x_mid,
            err_msg=f"req {rid}: w=0 guided diverged from unguided")
    for rid, da in res_a.decisions.items():
        db = res_b.decisions[rid]
        assert (da.action, da.effective_cut, da.kid) == \
            (db.action, db.effective_cut, db.kid), \
            f"req {rid}: w=0 admission decision diverged"
    print(f"w=0 anchor: {len(res_a.completions)} completions + "
          f"{len(res_a.decisions)} decisions bitwise equal", flush=True)

    # ---- mixed traffic: ONE scan program, zero new compiles -----------
    mix_names = ["ddpm", "ddpm_g", "ddim", "ddim_g"]
    eng.serve(reqs(mix_names, n_mix, salt=5))          # warmup
    n_compiled = eng._tick._cache_size()
    res_m = eng.serve(reqs(mix_names, n_mix, salt=7))
    new_compiles = eng._tick._cache_size() - n_compiled
    assert new_compiles == 0, \
        f"mixed guided traffic recompiled the scan ({new_compiles} new)"
    print(f"mixed: {res_m.summary['requests']} requests "
          f"({res_m.summary['images']} images) in "
          f"{res_m.summary['ticks']} ticks, 0 new scan compiles",
          flush=True)

    # ---- privacy: guided disclosures clear the floor, deterministically
    n_guided_served = 0
    for rid, dec in res_m.decisions.items():
        smp = samplers[dec.sampler]
        if dec.served and smp.guided:
            n_guided_served += 1
            assert dec.kid >= min_kid, \
                f"req {rid}: served guided KID {dec.kid} < {min_kid}"
            assert gate.disclosure_kid(dec.sampler,
                                       dec.effective_cut) >= min_kid
    assert n_guided_served > 0, "no guided request was served"
    gate2 = AdmissionPolicy(sched, calib, min_kid=min_kid,
                            samplers=samplers)
    res_m2 = engine(gate2).serve(reqs(mix_names, n_mix, salt=7))
    assert res_m.decisions == res_m2.decisions, \
        "guided admission decisions drifted across fresh gates"
    print(f"privacy: {n_guided_served} served guided requests all "
          f">= {min_kid:.5f}, fresh-gate decisions identical", flush=True)

    # ---- throughput: guided vs unguided at equal in-flight ------------
    # ungated engine: pure serving cost, and both traffics walk their
    # NOMINAL cut so the FLOP relation is exact (the gated engine may
    # bump guided and unguided requests to different effective cuts —
    # their KID landscapes differ at w != 0)
    eng_thr = engine(None)
    one = lambda i: 1
    eng_thr.serve(reqs(["ddpm"], n_thr, salt=9, batch_of=one,
                       cut=0.5))                            # warmup
    eng_thr.serve(reqs(["ddpm_g"], n_thr, salt=9, batch_of=one, cut=0.5))
    res_u = eng_thr.serve(reqs(["ddpm"], n_thr, salt=11, batch_of=one,
                               cut=0.5))
    res_g = eng_thr.serve(reqs(["ddpm_g"], n_thr, salt=11, batch_of=one,
                               cut=0.5))
    ratio = (res_g.summary["ticks_per_s"] /
             max(res_u.summary["ticks_per_s"], 1e-9))
    print("traffic,ticks,wall_s,ticks_per_s,server_flops")
    for label, res in (("unguided", res_u), ("guided", res_g)):
        print(f"{label},{res.summary['ticks']},{res.wall_s:.3f},"
              f"{res.summary['ticks_per_s']:.1f},"
              f"{res.summary['server_flops']:.3g}")
    print(f"guided/unguided ticks/sec: {ratio:.2f}x "
          f"(gate: >= 0.45 on the full run)", flush=True)
    assert res_g.summary["server_flops"] == \
        2.0 * res_u.summary["server_flops"], "guided server FLOPs != 2x"

    rec = {"scenario": "cfg_guidance", "toy": bool(args.toy),
           "slots": slots, "T": T, "K": K, "num_classes": NC,
           "cuts": list(cuts), "n_mixed": n_mix, "n_anchor": n_anchor,
           "n_throughput": n_thr, "min_kid": min_kid,
           "w0_bitwise_equal": True, "mixed_new_compiles": 0,
           "guided_served": n_guided_served,
           "ticks_unguided": res_u.summary["ticks"],
           "ticks_guided": res_g.summary["ticks"],
           "ticks_per_s_unguided": res_u.summary["ticks_per_s"],
           "ticks_per_s_guided": res_g.summary["ticks_per_s"],
           "throughput_ratio": ratio,
           "guidance_scales": {nm: samplers[nm].w for nm in samplers
                               if samplers[nm].guided},
           "occupancy_by_class_mixed":
               res_m.summary.get("occupancy_by_class", {}),
           "equivalence": "w=0 guided == unguided bitwise (completions + "
                          "admission decisions); mixed traffic one scan "
                          "program; fresh-gate decisions identical"}
    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "BENCH_cfg.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"# wrote {out}")
    if not args.toy:
        # issue gate: a guided lane pair costs one extra model lane, not
        # a second dispatch — >= 0.45x the unguided tick rate
        assert ratio >= 0.45, \
            f"guided serving only {ratio:.2f}x unguided ticks/sec"
    return rec


def bench_obs_overhead(args):
    """Observability-cost gate: the ``repro.obs`` stack (tracing + metrics
    registry + per-request timelines) threaded through the k-tick
    double-buffered engine must be FREE when off and near-free when on.

    Gates:

    * obs OFF is the pre-obs engine, bitwise: completions AND admission
      decisions identical to an ``obs=None`` run (always asserted — the
      exact per-tick utilization accounting is unconditional, so even the
      summary's utilization_mean must agree);
    * obs ON (trace + registry + timelines + JSONL snapshots) costs <= 5%
      ticks/sec with 256 in-flight requests churning through 32 slots
      (enforced on the full run only — CPU wall-clock noise at toy scale);
    * the exported trace validates against the Chrome trace-event schema
      and contains a ``dispatch`` phase span for EVERY window the engine
      ran, plus ``sync_wait``/``retire``/``admit`` host-loop phases;
    * every timeline walks queued -> ... -> retired in stage order, and
      the metrics JSONL parses with the expected instrument names.

    Writes results/BENCH_obs.json plus the sample artifacts
    results/obs_trace.json and results/obs_metrics.jsonl that the CI
    bench-smoke job uploads."""
    import dataclasses

    import numpy as np

    from repro.diffusion.sampler import make_sampler
    from repro.diffusion.schedule import cosine_schedule
    from repro.obs import (ObsConfig, load_trace, read_jsonl,
                           validate_events)
    from repro.serve import (AdmissionPolicy, EngineConfig, Request,
                             ServeEngine)

    T, K = (10, 5) if args.toy else (50, 10)
    slots = 8 if args.toy else 32
    n_req = 24 if args.toy else 256
    k_hot, depth = 8, 2
    size = 8
    shape = (size, size, 1)
    cut_ratios = (0.25, 0.5, 0.75)
    init_fn, apply_fn = _tiny_mlp_eps_model(size)

    sched = cosine_schedule(T)
    server_params = init_fn(jax.random.PRNGKey(0))
    samplers = {"ddpm": make_sampler(T),
                "ddim": make_sampler(T, "ddim", K, eta=0.0)}

    def requests():
        return [Request(req_id=i, key=jax.random.fold_in(
                            jax.random.PRNGKey(7), i),
                        batch=1, cut_ratio=cut_ratios[i % len(cut_ratios)],
                        sampler=("ddpm", "ddim")[i % 2])
                for i in range(n_req)]

    def admission():
        # median ddim floor => a mix of admit and bump decisions whose
        # replay the obs-on run must not perturb
        calib = jnp.tanh(jax.random.normal(jax.random.PRNGKey(5),
                                           (8,) + shape))
        probe = AdmissionPolicy(sched, calib, min_kid=float("-inf"),
                                samplers=samplers,
                                server_fn=functools.partial(apply_fn,
                                                            server_params))
        return probe.with_min_kid(float(np.median(probe.profile("ddim"))))

    os.makedirs(RESULTS, exist_ok=True)
    trace_path = os.path.join(RESULTS, "obs_trace.json")
    metrics_path = os.path.join(RESULTS, "obs_metrics.jsonl")
    if os.path.exists(metrics_path):
        os.remove(metrics_path)                 # JSONL appends across runs
    obs_cfg = ObsConfig(trace_path=trace_path, metrics_path=metrics_path,
                        metrics_every=4)
    base_cfg = EngineConfig(sched=sched, apply_fn=apply_fn,
                            image_shape=shape, slots=slots,
                            samplers=samplers, ticks_per_dispatch=k_hot,
                            async_depth=depth)

    def run(obs):
        eng = ServeEngine(dataclasses.replace(
            base_cfg, admission=admission(), obs=obs), server_params)
        eng.serve(requests())                         # compile + warmup
        if eng.obs:
            # the tracer accumulates across serve() calls — drop the warmup
            # spans so the span-per-window gate counts the timed run only
            eng.obs.tracer.clear()
        return eng.serve(requests()), eng

    print(f"# obs_overhead: {n_req} in-flight (batch 1, mixed ddpm/ddim, "
          f"KID-gated) on {slots} slots, T={T}, k={k_hot} depth={depth} — "
          f"obs off vs obs on (trace+registry+timelines+JSONL)")
    res_off, _ = run(None)                            # the pre-obs engine
    res_on, eng_on = run(obs_cfg)

    # ---- gate 1: obs off == obs on, bitwise ---------------------------
    assert set(res_on.completions) == set(res_off.completions)
    assert res_on.decisions == res_off.decisions, \
        "obs changed admission decisions"
    for rid, comp in res_off.completions.items():
        np.testing.assert_array_equal(res_on.completions[rid].x_mid,
                                      comp.x_mid,
                                      err_msg=f"req {rid} x_mid diverged")
    assert res_on.summary["ticks"] == res_off.summary["ticks"]
    assert (res_on.summary["utilization_mean"] ==
            res_off.summary["utilization_mean"]), \
        "exact utilization accounting must not depend on obs"
    assert res_off.timelines == {}, "obs=None must record no timelines"

    # ---- gate 2: trace validates + phase spans for every window -------
    events = load_trace(trace_path)
    n_events = validate_events(events)
    windows = res_on.summary["windows"]
    spans = {}
    for e in events:
        if e.get("ph") == "X":
            spans[e["name"]] = spans.get(e["name"], 0) + 1
    assert spans.get("dispatch", 0) == windows, \
        f"{spans.get('dispatch', 0)} dispatch spans != {windows} windows"
    for phase in ("sync_wait", "retire", "admit"):
        assert spans.get(phase, 0) >= 1, f"no {phase} span in trace"

    # ---- gate 3: timelines + metrics JSONL ----------------------------
    # every request gets a lifecycle (served OR rejected), in stage order
    order = {s: i for i, s in enumerate(
        ("queued", "scored", "admitted", "first_tick", "retired",
         "client_finished", "rejected"))}
    assert set(res_on.timelines) == set(range(n_req)), \
        "every request must have a timeline"
    for rid, tl in res_on.timelines.items():
        stages = [e["stage"] for e in tl]
        idx = [order[s] for s in stages]
        assert idx == sorted(idx) and len(set(stages)) == len(stages), \
            f"req {rid}: stages out of order: {stages}"
        assert stages[0] == "queued", stages
        served = res_on.decisions[rid].served
        assert ("retired" in stages) == served, (stages, served)
        assert ("rejected" in stages) == (not served), (stages, served)
    lines = read_jsonl(metrics_path)
    assert lines and lines[-1].get("final"), "metrics JSONL missing"
    names = set(lines[-1]["metrics"])
    for want in ("serve_ticks_total", "serve_retired_total",
                 "serve_latency_ticks", "serve_queue_depth"):
        assert want in names, f"{want} absent from registry snapshot"

    # ---- gate 4: ticks/sec overhead <= 5% (full run) ------------------
    tps_off = res_off.summary["ticks_per_s"]
    tps_on = res_on.summary["ticks_per_s"]
    overhead = 1.0 - tps_on / tps_off
    print("obs,ticks,wall_s,ticks_per_s")
    print(f"off,{res_off.summary['ticks']},{res_off.wall_s:.3f},"
          f"{tps_off:.1f}")
    print(f"on,{res_on.summary['ticks']},{res_on.wall_s:.3f},{tps_on:.1f}")
    print(f"bitwise equal; {n_events} trace events "
          f"({spans['dispatch']} dispatch spans = {windows} windows); "
          f"{len(lines)} metric snapshots; "
          f"obs overhead {overhead * 100:+.1f}% ticks/sec", flush=True)

    rec = {"scenario": "obs_overhead", "toy": bool(args.toy),
           "slots": slots, "n_requests": n_req, "T": T, "k": k_hot,
           "async_depth": depth, "bitwise_equal": True,
           "ticks": res_on.summary["ticks"], "windows": windows,
           "ticks_per_s_off": tps_off, "ticks_per_s_on": tps_on,
           "overhead_frac": overhead, "trace_events": n_events,
           "phase_spans": spans, "metric_snapshots": len(lines),
           "timelines": len(res_on.timelines),
           "aging_promotions": res_on.summary.get("aging_promotions", 0)}
    out = os.path.join(RESULTS, "BENCH_obs.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"# wrote {out} (+ obs_trace.json, obs_metrics.jsonl)")
    if not args.toy:
        # issue gate: full observability costs <= 5% ticks/sec
        assert overhead <= 0.05, \
            f"obs costs {overhead * 100:.1f}% ticks/sec (> 5%)"
    return rec


def bench_finisher_overlap(args):
    """Streaming-client-finisher gate (``finish_mode="stream"``): the
    client segment dispatched at window boundaries WHILE server scan
    windows are in flight must change nothing but the clock.

    Gates:

    * DETERMINISTIC (toy + full): streamed ``x0`` is BITWISE equal to the
      post-drain ``_finish_clients`` reference on mixed DDPM/DDIM traffic
      across k∈{1,8} x finish_async_depth∈{1,2}, with KID admission ON
      and OFF (decisions must replay identically too);
    * DETERMINISTIC (toy + full): a streamed run's exported trace
      schema-validates and contains >= 1 ``client_finish_dispatch`` span
      STARTING BEFORE the final server ``dispatch`` span ends — overlap
      proven from the timeline, not inferred from the clock;
    * PERF (full only): end-to-end ``serve(requests, client_stack)`` wall
      >= 1.3x faster streaming vs drain at 256 in-flight requests
      churning through 32 slots (both warmed, identical workload).

    Writes results/BENCH_finisher.json (uploaded by CI bench-smoke)."""
    import dataclasses

    import numpy as np

    from repro.diffusion.sampler import make_sampler
    from repro.diffusion.schedule import cosine_schedule
    from repro.obs import ObsConfig, load_trace, validate_events
    from repro.optim import adamw
    from repro.serve import (AdmissionPolicy, EngineConfig, Request,
                             ServeEngine)

    T, K = (10, 5) if args.toy else (50, 10)
    slots = 8 if args.toy else 32
    n_req_bitwise = 12 if args.toy else 24
    n_req_perf = 48 if args.toy else 256
    k_hot, depth = 8, 2
    n_clients = 4
    # full scale runs a heavier backbone: the streamed finisher's win is
    # real client COMPUTE overlapped/deduplicated, so per-lane-step work
    # must dominate per-call dispatch overhead (the tiny toy model is
    # all fixed overhead — fine for the deterministic gates, meaningless
    # for the clock)
    size, hidden = (8, 64) if args.toy else (16, 256)
    shape = (size, size, 1)
    # client-heavy cuts — the privacy-tier regime (CollaFuse: higher cut
    # = less disclosure = more of the trajectory on-client): the
    # finisher segment must be big enough that how it is scheduled
    # moves the end-to-end clock
    cut_ratios = (0.7, 0.9, 0.95)
    init_fn, apply_fn = _tiny_mlp_eps_model(size, hidden=hidden)

    sched = cosine_schedule(T)
    server_params = init_fn(jax.random.PRNGKey(0))
    stack = adamw.tree_stack(
        [init_fn(kk) for kk in
         jax.random.split(jax.random.PRNGKey(3), n_clients)])
    samplers = {"ddpm": make_sampler(T),
                "ddim": make_sampler(T, "ddim", K, eta=0.0)}

    def requests(n):
        # production-shaped mix: strided DDIM majority (3:1) with dense
        # DDPM in every slot window.  This is exactly the traffic drain
        # finishing handles worst — its single batch runs EVERY lane to
        # the global max step count, so each cheap DDIM lane (a handful
        # of client steps) pays the dense-DDPM bound; the streamed
        # finisher's step-homogeneous waves pay only their own bound.
        return [Request(req_id=i, key=jax.random.fold_in(
                            jax.random.PRNGKey(7), i),
                        batch=1, cut_ratio=cut_ratios[i % len(cut_ratios)],
                        client_idx=i % n_clients,
                        sampler="ddpm" if i % 4 == 0 else "ddim")
                for i in range(n)]

    def admission():
        # median floor over the ddim disclosure profile: a mix of admit
        # and bump decisions the streamed finisher must replay bitwise
        calib = jnp.tanh(jax.random.normal(jax.random.PRNGKey(5),
                                           (8,) + shape))
        probe = AdmissionPolicy(sched, calib, min_kid=float("-inf"),
                                samplers=samplers,
                                server_fn=functools.partial(apply_fn,
                                                            server_params))
        return probe.with_min_kid(float(np.median(probe.profile("ddim"))))

    base_cfg = EngineConfig(sched=sched, apply_fn=apply_fn,
                            image_shape=shape, slots=slots,
                            samplers=samplers, async_depth=depth)

    def engine(mode, k, fdepth, admit, obs=None):
        return ServeEngine(dataclasses.replace(
            base_cfg, ticks_per_dispatch=k, finish_mode=mode,
            finish_async_depth=fdepth,
            admission=admission() if admit else None, obs=obs),
            server_params)

    print(f"# finisher_overlap: mixed ddpm/ddim through {slots} slots, "
          f"T={T}, cuts {cut_ratios} over {n_clients} clients — "
          f"stream vs drain client finish")

    # ---- gate 1: streamed x0 bitwise == post-drain reference ----------
    rec = {"scenario": "finisher_overlap", "toy": bool(args.toy),
           "slots": slots, "T": T, "n_clients": n_clients,
           "bitwise": {}, "perf": {}, "trace": {}}
    print("admission,k,finish_async_depth,finish_batches,overlap_frac")
    for admit in (False, True):
        for k in (1, k_hot):
            ref = engine("drain", k, 1, admit).serve(
                requests(n_req_bitwise), stack)
            for fdepth in (1, 2):
                res = engine("stream", k, fdepth, admit).serve(
                    requests(n_req_bitwise), stack)
                assert set(res.completions) == set(ref.completions)
                assert res.decisions == ref.decisions, \
                    "stream finish changed admission decisions"
                for rid, comp in ref.completions.items():
                    got = res.completions[rid]
                    assert got.client_finished and comp.client_finished
                    np.testing.assert_array_equal(
                        got.x_mid, comp.x_mid,
                        err_msg=f"req {rid} x_mid (admit={admit}, k={k})")
                    np.testing.assert_array_equal(
                        got.x0, comp.x0,
                        err_msg=f"req {rid} x0 (admit={admit}, k={k}, "
                                f"fdepth={fdepth})")
                label = (f"admission_{'on' if admit else 'off'}"
                         f"_k{k}_fd{fdepth}")
                rec["bitwise"][label] = {
                    "bitwise_equal": True,
                    "finish_batches": res.summary["finish_batches"],
                    "overlap_frac": res.summary["overlap_frac"]}
                print(f"{'on' if admit else 'off'},{k},{fdepth},"
                      f"{res.summary['finish_batches']},"
                      f"{res.summary['overlap_frac']:.2f}")
    print("bitwise: streamed x0 == post-drain reference on every config",
          flush=True)

    # ---- gate 2: overlap proven from the exported trace ---------------
    os.makedirs(RESULTS, exist_ok=True)
    trace_path = os.path.join(RESULTS, "finisher_trace.json")
    # perf-sized workload: the coalescing finisher only dispatches
    # in-loop once a class bucket holds ~two windows' worth of lanes, so
    # the overlap proof needs enough churn to cross that threshold mid-run
    engine("stream", k_hot, 2, False,
           obs=ObsConfig(trace_path=trace_path)).serve(
        requests(n_req_perf), stack)
    events = load_trace(trace_path)
    n_events = validate_events(events)
    disp = [e for e in events
            if e.get("ph") == "X" and e["name"] == "dispatch"]
    fin = [e for e in events
           if e.get("ph") == "X" and e["name"] == "client_finish_dispatch"]
    assert disp and fin, "trace missing dispatch/client_finish_dispatch"
    last_disp_end = max(e["ts"] + e["dur"] for e in disp)
    overlapped = [e for e in fin if e["ts"] < last_disp_end]
    assert overlapped, \
        "no client_finish_dispatch span starts before the final server " \
        "dispatch span ends — the stream finisher never overlapped"
    rec["trace"] = {"events": n_events, "dispatch_spans": len(disp),
                    "finish_dispatch_spans": len(fin),
                    "overlapped_finish_spans": len(overlapped)}
    print(f"trace: {n_events} events validate; {len(overlapped)}/"
          f"{len(fin)} client_finish_dispatch spans start before the "
          f"final dispatch span ends", flush=True)

    # ---- gate 3: end-to-end wall, stream vs drain (full only) ---------
    # paired trials: single-run wall on a shared box swings ±20%, and
    # background load can sit on one mode's whole measurement phase —
    # so interleave drain/stream runs and take the MEDIAN of per-pair
    # ratios (drift slower than one pair cancels; no lucky outlier run
    # decides the gate)
    eng_d = engine("drain", k_hot, 2, False)
    eng_s = engine("stream", k_hot, 2, False)
    eng_d.serve(requests(n_req_perf), stack)          # compile + warmup
    eng_s.serve(requests(n_req_perf), stack)
    pairs = [(eng_d.serve(requests(n_req_perf), stack),
              eng_s.serve(requests(n_req_perf), stack))
             for _ in range(5)]
    pairs.sort(key=lambda p: p[0].wall_s / p[1].wall_s)
    res_drain, res_stream = pairs[len(pairs) // 2]
    speedup = res_drain.wall_s / res_stream.wall_s
    s = res_stream.summary
    rec["perf"] = {
        "n_requests": n_req_perf, "k": k_hot, "async_depth": depth,
        "finish_async_depth": 2,
        "drain_wall_s": res_drain.wall_s,
        "stream_wall_s": res_stream.wall_s,
        "drain_finish_s": res_drain.summary["finish_s"],
        "stream_finish_s": s["finish_s"],
        "stream_overlap_frac": s["overlap_frac"],
        "stream_finish_batches": s["finish_batches"],
        "speedup": speedup}
    print(f"perf ({n_req_perf} in-flight, k={k_hot}): drain "
          f"{res_drain.wall_s:.3f}s vs stream {res_stream.wall_s:.3f}s "
          f"-> {speedup:.2f}x (overlap_frac {s['overlap_frac']:.2f}, "
          f"{s['finish_batches']} finish batches)", flush=True)

    out = os.path.join(RESULTS, "BENCH_finisher.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"# wrote {out} (+ finisher_trace.json)")
    if not args.toy:
        # issue gate: streaming the client finish >= 1.3x end-to-end
        assert speedup >= 1.3, \
            f"stream finish only {speedup:.2f}x over drain (< 1.3x)"
    return rec


def bench_kernels(args):
    from repro.diffusion import ddpm as ddpm_mod
    from repro.diffusion.schedule import cosine_schedule
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(0)
    print("# kernels: Pallas (interpret mode on CPU) vs jnp oracle")
    print("name,us_per_call_kernel,us_per_call_ref,max_abs_err")
    rows = []

    # flash attention (B, S, H, HD) with GQA kv heads
    b, s, h, kv, hd = 2, 256, 8, 2, 64
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    f_k = jax.jit(functools.partial(ops.flash_attention, causal=True))
    f_r = jax.jit(functools.partial(ref.attention_ref, causal=True))
    us_k, out_k = _timeit(f_k, q, k, v)
    us_r, out_r = _timeit(f_r, q, k, v)
    err = float(jnp.abs(out_k - out_r).max())
    print(f"flash_attention,{us_k:.0f},{us_r:.0f},{err:.2e}")
    rows.append(("flash_attention", err, 2e-4))

    # ssm scan: x (B,S,NH,P), dt (B,S,NH), a (NH,), bm/cm (B,S,N)
    b, s, nh, p, n = 2, 128, 8, 32, 16
    x = jax.random.normal(ks[3], (b, s, nh, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (b, s, nh), jnp.float32))
    a = -jnp.exp(jax.random.normal(ks[0], (nh,)) * 0.3)
    bm = jax.random.normal(ks[1], (b, s, n), jnp.float32)
    cm = jax.random.normal(ks[2], (b, s, n), jnp.float32)
    f_k = jax.jit(functools.partial(ops.ssm_scan, chunk=32, head_block=8))
    f_r = jax.jit(ref.ssm_scan_ref)
    us_k, out_k = _timeit(f_k, x, dt, a, bm, cm)
    us_r, out_r = _timeit(f_r, x, dt, a, bm, cm)
    err = float(jnp.abs(out_k - out_r).max())
    print(f"ssm_scan,{us_k:.0f},{us_r:.0f},{err:.2e}")
    rows.append(("ssm_scan", err, 1e-3))

    # fused ddpm sampling step vs p_sample
    sched = cosine_schedule(100)
    shp = (8, 32, 32, 1)
    x_t = jax.random.normal(ks[0], shp, jnp.float32)
    eps_hat = jax.random.normal(ks[1], shp, jnp.float32)
    noise = jax.random.normal(ks[2], shp, jnp.float32)
    t = jnp.full((8,), 50, jnp.int32)
    f_k = jax.jit(lambda x1, t1, e1, n1: ops.ddpm_step(sched, x1, t1, e1, n1))
    f_r = jax.jit(lambda x1, t1, e1, n1: ddpm_mod.p_sample(sched, x1, t1,
                                                           e1, n1))
    us_k, out_k = _timeit(f_k, x_t, t, eps_hat, noise)
    us_r, out_r = _timeit(f_r, x_t, t, eps_hat, noise)
    err = float(jnp.abs(out_k - out_r).max())
    print(f"ddpm_step,{us_k:.0f},{us_r:.0f},{err:.2e}")
    rows.append(("ddpm_step", err, 1e-4))

    for name, err, tol in rows:
        assert err < tol, f"{name} diverged from oracle: {err} >= {tol}"
    return rows


# ---------------------------------------------------------------------------
# Roofline table from the dry-run artefacts
# ---------------------------------------------------------------------------
def bench_roofline(args):
    if not os.path.isdir(DRYRUN):
        print("# roofline: results/dryrun missing — run "
              "`python -m repro.launch.dryrun --sweep` first")
        return []
    files = sorted(f for f in os.listdir(DRYRUN) if f.endswith(".json"))
    print("# roofline: per (arch x shape x mesh) from dry-run artefacts")
    print("arch,shape,mesh,compute_s,memory_s,collective_s,dominant,"
          "useful_flops_ratio")
    rows = []
    for fn in files:
        with open(os.path.join(DRYRUN, fn)) as f:
            rec = json.load(f)
        r = rec.get("roofline")
        if not r:
            continue
        rows.append(rec)
        print(f"{rec['arch']},{rec['shape']},{rec['mesh']},"
              f"{r['compute_s']:.5f},{r['memory_s']:.5f},"
              f"{r['collective_s']:.5f},{r['dominant']},"
              f"{r.get('useful_ratio', 0):.3f}")
    print(f"# {len(rows)} combos recorded")
    return rows


BENCHES = {
    "fig1_disclosure": bench_fig1_disclosure,
    "fig3_tradeoff": bench_fig3_tradeoff,
    "energy_split": bench_energy_split,
    "clients_scaling": bench_clients_scaling,
    "serve_continuous": bench_serve_continuous,
    "ddim_speedup": bench_ddim_speedup,
    "privacy_admission": bench_privacy_admission,
    "pod_ticks": bench_pod_ticks,
    "hetero_packing": bench_hetero_packing,
    "cfg_guidance": bench_cfg_guidance,
    "obs_overhead": bench_obs_overhead,
    "finisher_overlap": bench_finisher_overlap,
    "kernels": bench_kernels,
    "masked_step": bench_masked_step,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(BENCHES), default=None)
    ap.add_argument("--rounds", type=int, default=40,
                    help="training rounds per cut-ratio in fig3_tradeoff")
    ap.add_argument("--toy", action="store_true",
                    help="CI-smoke scale: tiny sweeps, no perf gates")
    args = ap.parse_args()
    names = [args.only] if args.only else list(BENCHES)
    t0 = time.time()
    for name in names:
        print(f"\n==== {name} ====", flush=True)
        BENCHES[name](args)
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
