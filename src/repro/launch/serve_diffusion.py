"""Diffusion serving launcher — the continuous-batching engine on a real
(data, model) mesh.

Runs the CollaFuse server segment for a stream of generation requests
(mixed cut-ratios / batch sizes / arrival ticks) through ONE jitted masked
denoise step per tick, with the slot array sharded over ``data`` and the
U-Net sharded via ``parallel/sharding.py``.  On a CPU host use
``--devices N`` to force N host devices::

    PYTHONPATH=src python -m repro.launch.serve_diffusion --devices 4 \
        --mesh-shape 4x1 --slots 16 --requests 32 --image 8 --T 20

``--model paper`` serves the paper's U-Net (``configs/paper_unet``) at its
published widths — the configuration for a TPU::

    PYTHONPATH=src python -m repro.launch.serve_diffusion --model paper \
        --T 100 --mix --slots 32 --requests 8

``--compare-sequential`` also times the per-request ``split_sample``
baseline and prints the continuous-batching speedup.
"""
import argparse
import contextlib
import json


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=2,
                    help="request batch sizes cycle 1..max-batch")
    ap.add_argument("--model", choices=["toy", "paper"], default="toy",
                    help="toy = a 2-level, 8-channel U-Net at --image "
                         "(CPU runs); paper = configs/paper_unet at its "
                         "published widths (128x128x1, 64 base channels, "
                         "mults 1/2/4/8, attention at 16x16)")
    ap.add_argument("--image", type=int, default=8,
                    help="image side of the toy model")
    ap.add_argument("--T", type=int, default=20)
    ap.add_argument("--cut-ratios", type=float, nargs="+",
                    default=[0.25, 0.5, 0.75])
    ap.add_argument("--clients", type=int, default=4,
                    help="private client models finishing t_split..1")
    ap.add_argument("--policy", choices=["fifo", "cut_ratio"],
                    default="cut_ratio")
    ap.add_argument("--step-backend", default=None,
                    choices=["jnp", "pallas", "pallas_masked"],
                    help="denoise-tick StepBackend; pallas_masked fuses the "
                         "whole masked tick into one kernel (compiled on a "
                         "TPU, interpreted elsewhere).  Default: the "
                         "platform's path — pallas_masked on a TPU, jnp "
                         "elsewhere")
    ap.add_argument("--sampler", default="ddpm", choices=["ddpm", "ddim"],
                    help="trajectory/update family requests walk: ddpm = "
                         "dense T-step chain; ddim = strided --num-steps "
                         "subsequence (the cut maps to the nearest "
                         "trajectory point)")
    ap.add_argument("--num-steps", type=int, default=0,
                    help="DDIM trajectory length K (0 = dense T steps)")
    ap.add_argument("--guidance", type=float, default=None,
                    help="classifier-free guidance scale w: adds a guided "
                         "'ddpm_g' menu entry and routes requests through "
                         "it (all of them, or cycled with the unguided "
                         "entries under --mix).  Guided requests occupy a "
                         "cond+uncond lane pair — 2x lanes, one model "
                         "dispatch.  Requires --num-classes > 0; w=0 is "
                         "the bitwise-vs-unguided correctness anchor")
    ap.add_argument("--num-classes", type=int, default=0,
                    help="class-conditional U-Net: N real labels + a null "
                         "row (index N) added to the time embedding.  0 "
                         "keeps the unconditional model (bitwise the old "
                         "path)")
    ap.add_argument("--eta", type=float, default=0.0,
                    help="DDIM stochasticity in [0,1]; 1 on the dense "
                         "trajectory is the DDPM ancestral step")
    ap.add_argument("--mix", action="store_true",
                    help="heterogeneous traffic: requests cycle over the "
                         "WHOLE sampler menu (dense ddpm + a strided ddim; "
                         "+ the ad-hoc entry under --spare-columns) and "
                         "--cut-ratios, instead of walking one --sampler. "
                         "Pair with --pack for step-homogeneous waves")
    ap.add_argument("--pack", action="store_true",
                    help="trajectory-aware wave packing in the scheduler: "
                         "same-(sampler, cut-class) candidates behind the "
                         "head coalesce into each scan window's freed-slot "
                         "budget (admission order changes, completions are "
                         "bitwise unchanged)")
    ap.add_argument("--spare-columns", type=int, default=0,
                    help="preallocate N spare coefficient-table columns so "
                         "ServeEngine.register_sampler can add ad-hoc "
                         "trajectories at serve boundaries with ZERO "
                         "recompiles; the launcher registers a 'dyn' ddim "
                         "trajectory and (with --mix) routes requests "
                         "through it to prove the cache held")
    ap.add_argument("--min-kid", type=float, default=None,
                    help="KID-gated admission floor: score each request's "
                         "disclosure on a calibration batch before it takes "
                         "a slot; below-floor requests are bumped to a "
                         "noisier cut or rejected.  Default None = gate off "
                         "(the pre-gate engine path, bitwise)")
    ap.add_argument("--calib", type=int, default=16,
                    help="calibration batch size for the admission gate "
                         "(synthetic client images; needs >= 2)")
    ap.add_argument("--ticks-per-dispatch", type=int, default=1,
                    help="k denoise ticks fused per device call under "
                         "lax.scan; retire/refill happen at window "
                         "boundaries (up to k-1 extra ticks of latency for "
                         "k fewer host round-trips per tick)")
    ap.add_argument("--async-depth", type=int, default=1,
                    help="scan windows in flight: 1 = synchronous, 2 = "
                         "double-buffered (dispatch window N+1 while "
                         "window N's done-mask is in flight)")
    ap.add_argument("--finish-mode", choices=["stream", "drain"],
                    default="stream",
                    help="client segment path: stream = dispatch grouped "
                         "finish batches at each window boundary while "
                         "later server windows are in flight (default); "
                         "drain = one reference pass after the server "
                         "queue empties.  x0 is bitwise identical either "
                         "way")
    ap.add_argument("--finish-async-depth", type=int, default=1,
                    help="streamed finish batches in flight before the "
                         "oldest is synced (the client-segment analogue "
                         "of --async-depth)")
    ap.add_argument("--arrival-every", type=int, default=0,
                    help="0 = all at tick 0; k = one request every k ticks")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU dry environments)")
    ap.add_argument("--mesh-shape", default="",
                    help="DxM, e.g. 4x1; default = all devices on data axis")
    ap.add_argument("--compare-sequential", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="",
                    help="write the serve summary to this path")
    ap.add_argument("--trace-out", default="",
                    help="export a Chrome trace-event JSON of the host "
                         "loop's phase spans + per-request tracks (load in "
                         "chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default="",
                    help="append registry snapshots (JSON-lines) at every "
                         "--metrics-every window boundaries")
    ap.add_argument("--metrics-every", type=int, default=1,
                    help="snapshot cadence in windows for --metrics-out")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of the warm serve "
                         "call into this directory; the host loop's spans "
                         "are in it, on the device ops' clock")
    return ap.parse_args(argv)


def unet_config(args):
    """The served U-Net: the paper's (``--model paper``) or the CPU toy."""
    import dataclasses

    from repro.configs.base import UNetConfig
    from repro.configs.paper_unet import CONFIG
    if args.model == "paper":
        return dataclasses.replace(CONFIG, num_classes=args.num_classes)
    return dataclasses.replace(
        UNetConfig().reduced(), image_size=args.image, base_channels=8,
        channel_mults=(1, 2), n_res_blocks=1, attn_resolutions=(),
        time_dim=32, norm_groups=4, num_classes=args.num_classes)


def build_engine(args, mesh):
    """Everything :func:`main` serves, built from its arguments: returns
    ``(engine, requests, client_stack, dyn_sampler)``.  ``mesh``
    None keeps the slot state on the default device.  Weights come from
    ``unet.init_params`` under ``--seed``; no file is read."""
    import jax

    from repro.diffusion.backend import get_backend
    from repro.diffusion.sampler import make_sampler
    from repro.diffusion.schedule import cosine_schedule
    from repro.models import unet
    from repro.models.layers import ShardCtx
    from repro.optim import adamw
    from repro.parallel import sharding as shd
    from repro.serve import (EngineConfig, Request, ServeEngine,
                             make_scheduler)

    if args.sampler == "ddpm" and args.num_steps:
        raise SystemExit("--num-steps strides the chain, which needs "
                         "--sampler ddim (ddpm is dense-only)")
    if args.guidance is not None and args.num_classes <= 0:
        raise SystemExit("--guidance needs a conditional model: pass "
                         "--num-classes N (labels 0..N-1, null row N)")
    samplers = {"ddpm": make_sampler(args.T)}
    if args.sampler == "ddim" or args.mix:
        samplers["ddim"] = make_sampler(
            args.T, "ddim", args.num_steps or max(2, args.T // 2),
            args.eta)
    if args.guidance is not None:
        samplers["ddpm_g"] = make_sampler(args.T, guidance=args.guidance)
    dyn_sampler = None
    if args.spare_columns:
        k_dyn = min(args.spare_columns, max(2, args.T // 4))
        dyn_sampler = make_sampler(args.T, "ddim", k_dyn, args.eta)
    request_samplers = ["ddpm_g" if args.guidance is not None
                        else args.sampler]
    if args.mix:
        # heterogeneous traffic cycles the WHOLE menu: guided x unguided
        # x (below) every --cut-ratios value
        request_samplers = list(samplers) + (["dyn"] if dyn_sampler
                                             else [])
    traffic = ("mix of " + "/".join(request_samplers) if args.mix
               else samplers[request_samplers[0]].describe())
    d, m = (mesh.shape["data"], mesh.shape["model"]) if mesh is not None \
        else (1, 1)
    ucfg = unet_config(args)
    image_shape = (ucfg.image_size, ucfg.image_size, ucfg.in_channels)
    print(f"serve_diffusion: model={args.model} "
          f"image={'x'.join(map(str, image_shape))} "
          f"mesh=data:{d}xmodel:{m} slots={args.slots} "
          f"requests={args.requests} T={args.T} policy={args.policy} "
          f"backend={get_backend(args.step_backend).name} "
          f"sampler={traffic} "
          f"pack={args.pack} spare_columns={args.spare_columns} "
          f"min_kid={args.min_kid} guidance={args.guidance} "
          f"num_classes={args.num_classes}")

    if args.num_classes > 0:
        apply_fn = lambda p, x, t, y=None: unet.forward(p, x, t, ucfg, y)
    else:
        apply_fn = lambda p, x, t: unet.forward(p, x, t, ucfg)
    sched = cosine_schedule(args.T)

    key = jax.random.PRNGKey(args.seed)
    k_s, k_c, k_r = jax.random.split(key, 3)
    server_params = unet.init_params(k_s, ucfg)
    if mesh is not None:
        ctx = ShardCtx(mesh=mesh, batch_axes=("data",))
        server_params = jax.device_put(
            server_params,
            shd.to_shardings(shd.param_specs(server_params, ctx), mesh))
    client_stack = adamw.tree_stack(
        [unet.init_params(k, ucfg)
         for k in jax.random.split(k_c, args.clients)])

    requests = [
        Request(req_id=i, key=jax.random.fold_in(k_r, i),
                batch=1 + i % args.max_batch,
                cut_ratio=args.cut_ratios[i % len(args.cut_ratios)],
                client_idx=i % args.clients,
                arrival_tick=i * args.arrival_every,
                sampler=request_samplers[i % len(request_samplers)],
                label=(i % args.num_classes) if args.num_classes
                      else 0)
        for i in range(args.requests)
    ]

    admission = None
    if args.min_kid is not None:
        from repro.data.synthetic import (ClientDataConfig,
                                          make_client_datasets)
        from repro.serve import AdmissionPolicy
        calib_sets, _ = make_client_datasets(ClientDataConfig(
            n_clients=1, per_client=args.calib,
            image_size=ucfg.image_size, holdout=2, seed=args.seed))
        admission = AdmissionPolicy(sched, calib_sets[0],
                                    min_kid=args.min_kid,
                                    samplers=samplers)
    obs = None
    if args.trace_out or args.metrics_out or args.profile_dir:
        from repro.serve import ObsConfig
        obs = ObsConfig(
            trace_path=args.trace_out or None,
            metrics_path=args.metrics_out or None,
            metrics_every=args.metrics_every)
    cfg = EngineConfig(
        sched=sched, apply_fn=apply_fn, image_shape=image_shape,
        slots=args.slots,
        scheduler=make_scheduler(args.policy, args.T, samplers=samplers,
                                 pack=args.pack),
        step_backend=args.step_backend, mesh=mesh, samplers=samplers,
        admission=admission, spare_columns=args.spare_columns,
        ticks_per_dispatch=args.ticks_per_dispatch,
        async_depth=args.async_depth, finish_mode=args.finish_mode,
        finish_async_depth=args.finish_async_depth, obs=obs,
        num_classes=args.num_classes)
    eng = ServeEngine(cfg, server_params)
    if dyn_sampler is not None:
        eng.register_sampler("dyn", dyn_sampler)
    return eng, requests, client_stack, dyn_sampler


def main(argv=None):
    args = _parse_args(argv)
    from repro.launch.mesh import host_mesh
    mesh = host_mesh(args.mesh_shape, force_devices=args.devices)

    import jax

    from repro.launch.compile_cache import use_compile_cache
    from repro.serve import time_sequential

    use_compile_cache()
    with jax.set_mesh(mesh):
        eng, requests, client_stack, dyn_sampler = build_engine(args, mesh)
        eng.serve(list(requests), client_stack)            # compile + warmup
        n_compiled = eng._tick._cache_size()
        if dyn_sampler is not None:
            # ad-hoc re-registration at the serve boundary: one device
            # scatter into the spare columns, zero new scan compiles
            eng.register_sampler("dyn", dyn_sampler)
        with (jax.profiler.trace(args.profile_dir) if args.profile_dir
              else contextlib.nullcontext()):
            res = eng.serve(list(requests), client_stack)  # warm jit cache
        if dyn_sampler is not None:
            assert eng._tick._cache_size() == n_compiled, \
                "dynamic sampler registration recompiled the scan program"
            print(f"dynamic menu: {eng.registered_samplers()} "
                  f"(dyn={dyn_sampler.describe()}, 0 new scan compiles)",
                  flush=True)
        s = res.summary
        print(f"engine: {s['requests']} requests ({s['images']} images) in "
              f"{res.wall_s:.2f}s over {s['ticks']} ticks | "
              f"{s['requests_per_s']:.1f} req/s | "
              f"p50/p95 latency {s['latency_ticks_p50']:.0f}/"
              f"{s['latency_ticks_p95']:.0f} ticks | "
              f"util {s['utilization_mean']:.2f}", flush=True)
        print(f"client finish ({s['finish_mode']}): "
              f"{s['finish_s'] * 1e3:.1f}ms in {s['finish_batches']} "
              f"batch(es) of {s['finish_programs']} program(s), "
              f"overlap_frac {s['overlap_frac']:.2f} "
              f"(tail {s['finish_tail_s'] * 1e3:.1f}ms)", flush=True)
        if "fragmentation_frac" in s:
            occ = s.get("occupancy_by_class", {})
            top = ", ".join(
                f"{c}:{v}" for c, v in
                sorted(occ.items(), key=lambda kv: -kv[1])[:4])
            print(f"slot pool (pack={args.pack}): fragmentation_frac "
                  f"{s['fragmentation_frac']:.4f} | occupancy by class "
                  f"(lane-ticks): {top}", flush=True)
        if eng.admission is not None:
            a = s["admission"]
            dk = a.get("disclosure_kid", {})
            print(f"admission (min_kid={args.min_kid}): "
                  f"{a['admitted']} admitted, {a['bumped']} bumped, "
                  f"{a['rejected']} rejected | served disclosure KID "
                  f"min/mean {dk.get('min', 0):.4f}/{dk.get('mean', 0):.4f}",
                  flush=True)
            for d in res.rejected.values():
                print(f"  rejected req {d.req_id}: {d.describe()}")
        for comp in res.completions.values():
            assert comp.x0 is not None and bool(
                jax.numpy.isfinite(jax.numpy.asarray(comp.x0)).all()), \
                f"non-finite output for request {comp.request.req_id}"

        if eng.obs and res.timelines:
            rid = min(res.timelines)
            print(f"request {rid} lifecycle: " + " -> ".join(
                f"{e['stage']}@t{e['tick']}" if "tick" in e else e["stage"]
                for e in res.timelines[rid]), flush=True)
        if args.trace_out:
            print(f"wrote trace {args.trace_out} "
                  f"({len(eng.obs.tracer.events())} events)")
        if args.metrics_out:
            print(f"wrote metrics {args.metrics_out}")

        if args.compare_sequential:
            seq_s = time_sequential(eng.config, requests, eng.server_params,
                                    client_stack)
            s["sequential_s"] = seq_s
            s["speedup_vs_sequential"] = seq_s / res.wall_s
            print(f"sequential split_sample: {seq_s:.2f}s -> "
                  f"speedup {seq_s / res.wall_s:.2f}x", flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(s, f, indent=1)
        print(f"wrote {args.json}")
    print("serve_diffusion OK")


if __name__ == "__main__":
    main()
