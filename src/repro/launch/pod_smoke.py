"""Multi-process pod serving smoke: N ``jax.distributed`` host processes,
ONE shared request queue, per-host lane ownership.

Every process runs the identical deterministic control loop (SPMD
bookkeeping — admissions, retires, windows all replay bitwise because the
done-mask is gathered/replicated across the pod), but each host
materializes only the cut tensors of the lanes it OWNS
(``parallel.sharding.lane_owners``).  Each process writes a JSON artifact
of its owned rows; the union across hosts must reassemble the single-host
engine result bitwise — that is the check ``tests/test_serve.py``'s slow
2-process smoke performs.

Run one process per host (CPU container; gloo collectives)::

    PYTHONPATH=src python -m repro.launch.pod_smoke \
        --coordinator 127.0.0.1:12355 --num-processes 2 --process-id 0 \
        --out /tmp/pod0.json &
    PYTHONPATH=src python -m repro.launch.pod_smoke \
        --coordinator 127.0.0.1:12355 --num-processes 2 --process-id 1 \
        --out /tmp/pod1.json

``--num-processes 1`` skips ``jax.distributed`` entirely and serves the
same workload in-process — the reference artifact.
"""
import argparse
import json

T = 10
SIZE = 6
SHAPE = (SIZE, SIZE, 1)
NUM_CLASSES = 3          # conditional world: labels 0..2, null row 3
GUIDANCE_W = 1.5         # the menu's guided entry (ddpm_g)


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", default="127.0.0.1:12355")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--ticks-per-dispatch", type=int, default=4)
    ap.add_argument("--async-depth", type=int, default=2)
    ap.add_argument("--clients", type=int, default=0,
                    help="serve with a deterministic stacked client "
                         "model of this many rows so the CLIENT segment "
                         "runs too (0 = server segment only, the "
                         "classic artifact)")
    ap.add_argument("--finish-mode", choices=["stream", "drain"],
                    default="stream",
                    help="with --clients: stream = overlap client finish "
                         "batches with in-flight server windows; drain = "
                         "reference post-drain pass (bitwise identical)")
    ap.add_argument("--finish-async-depth", type=int, default=1,
                    help="streamed finish batches in flight before the "
                         "oldest is synced")
    ap.add_argument("--pack", action="store_true",
                    help="trajectory-aware wave packing at admission: the "
                         "deterministic scheduler walk replays identically "
                         "on every host, so the pod artifact stays bitwise "
                         "— only admission ticks move")
    ap.add_argument("--trace-out", default="",
                    help="per-host Chrome trace export: host i writes "
                         "<path>.host<i> with pid=i-tagged events, so "
                         "repro.obs.merge_traces folds a pod run into ONE "
                         "Perfetto timeline (one lane per host)")
    return ap.parse_args(argv)


def build_world():
    """Deterministic (sched, apply_fn, server_params, samplers) — identical
    on every process, and importable by the test for the reference run."""
    import jax
    import jax.numpy as jnp

    from repro.diffusion.sampler import make_sampler
    from repro.diffusion.schedule import cosine_schedule

    d = SIZE * SIZE
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    server = {"w1": jax.random.normal(ks[0], (d + 8, 32)) / 6.0,
              "w2": jax.random.normal(ks[1], (32, d)) / 6.0,
              # class conditioning: one embedding row per label + a null
              # row (index NUM_CLASSES) added to the 8-dim time embedding
              "yemb": jax.random.normal(
                  ks[2], (NUM_CLASSES + 1, 8)) / 6.0}

    def apply_fn(p, x, t, y=None):
        b = x.shape[0]
        freqs = jnp.exp(jnp.linspace(0.0, 3.0, 4))
        ang = t[:, None].astype(jnp.float32) * freqs[None]
        temb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
        yc = (jnp.full((b,), NUM_CLASSES, jnp.int32) if y is None
              else jnp.clip(y, 0, NUM_CLASSES))
        temb = temb + p["yemb"][yc]
        h = jax.nn.silu(
            jnp.concatenate([x.reshape(b, -1), temb], -1) @ p["w1"])
        return (h @ p["w2"]).reshape(x.shape)

    samplers = {"ddpm": make_sampler(T),
                "ddim5": make_sampler(T, "ddim", 5, eta=0.0),
                "ddpm_g": make_sampler(T, guidance=GUIDANCE_W)}
    return cosine_schedule(T), apply_fn, server, samplers


def build_client_stack(n_clients):
    """Deterministic [n_clients, ...] stacked private models matching
    :func:`build_world`'s apply_fn — identical on every process, so the
    streamed client finish replays bitwise across the pod."""
    import jax

    from repro.optim import adamw
    d = SIZE * SIZE

    def one(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"w1": jax.random.normal(k1, (d + 8, 32)) / 6.0,
                "w2": jax.random.normal(k2, (32, d)) / 6.0,
                "yemb": jax.random.normal(
                    k3, (NUM_CLASSES + 1, 8)) / 6.0}
    return adamw.tree_stack(
        [one(k) for k in
         jax.random.split(jax.random.PRNGKey(3), n_clients)])


def build_requests(n):
    import jax

    from repro.serve import Request
    # index 2 mod 3 routes through the guided menu entry — every smoke
    # (n >= 3) carries at least one cond+uncond lane pair through the pod
    return [Request(req_id=i, key=jax.random.fold_in(jax.random.PRNGKey(7), i),
                    batch=1 + i % 2, cut_ratio=(0.25, 0.5, 0.75)[i % 3],
                    client_idx=0, arrival_tick=i % 3,
                    sampler=("ddpm", "ddim5", "ddpm_g")[i % 3],
                    label=i % NUM_CLASSES)
            for i in range(n)]


def serve_pod(num_processes, process_id, slots, n_requests, k, depth,
              mesh=None, trace_out="", clients=0, finish_mode="stream",
              finish_async_depth=1, pack=False):
    """Build the pod engine and serve the canonical workload; returns the
    ServeResult.  ``mesh=None`` runs hostless (the in-process reference).
    ``trace_out`` turns on obs tracing: each host exports its own
    pid-tagged trace (``<path>.host<i>`` under multiple processes) for a
    later :func:`repro.obs.merge_traces` into one pod timeline.
    ``clients`` > 0 adds a deterministic stacked client model so the
    client segment runs too — streamed against in-flight server windows
    or drained afterwards per ``finish_mode``."""
    from repro.serve import EngineConfig, FIFOScheduler, ObsConfig, \
        ServeEngine
    sched, apply_fn, server, samplers = build_world()
    obs = ObsConfig(trace_path=trace_out) if trace_out else None
    cfg = EngineConfig(sched=sched, apply_fn=apply_fn, image_shape=SHAPE,
                       slots=slots, samplers=samplers, mesh=mesh,
                       scheduler=FIFOScheduler(pack=pack) if pack else None,
                       ticks_per_dispatch=k, async_depth=depth,
                       hosts=num_processes,
                       host_id=process_id if num_processes > 1 else 0,
                       finish_mode=finish_mode,
                       finish_async_depth=finish_async_depth,
                       obs=obs, num_classes=NUM_CLASSES)
    stack = build_client_stack(clients) if clients else None
    return ServeEngine(cfg, server).serve(build_requests(n_requests),
                                          stack)


def artifact(res, process_id):
    """Owned rows only, exact float lists — what this host disclosed."""
    out = {"process_id": process_id, "completions": {}}
    for rid, comp in sorted(res.completions.items()):
        owned = [int(i) for i in range(comp.request.batch)
                 if bool(comp.owned[i])]
        rec = {
            "owned": owned,
            "retire_tick": int(comp.retire_tick),
            "rows": {str(i): [float(v) for v in comp.x_mid[i].ravel()]
                     for i in owned},
        }
        if comp.client_finished:
            rec["x0_rows"] = {
                str(i): [float(v) for v in comp.x0[i].ravel()]
                for i in owned}
        out["completions"][str(rid)] = rec
    out["summary"] = {kk: res.summary[kk]
                      for kk in ("served", "images", "ticks", "windows")}
    return out


def main(argv=None):
    args = _parse_args(argv)
    import jax
    if args.num_processes > 1:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=args.coordinator,
                                   num_processes=args.num_processes,
                                   process_id=args.process_id)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((jax.device_count(),), ("data",))
    else:
        mesh = None

    res = serve_pod(args.num_processes, args.process_id, args.slots,
                    args.requests, args.ticks_per_dispatch,
                    args.async_depth, mesh=mesh, trace_out=args.trace_out,
                    clients=args.clients, finish_mode=args.finish_mode,
                    finish_async_depth=args.finish_async_depth,
                    pack=args.pack)
    if args.clients:
        s = res.summary
        print(f"client finish ({s['finish_mode']}): "
              f"{s['finish_batches']} batch(es) of "
              f"{s['finish_programs']} program(s), "
              f"overlap_frac {s['overlap_frac']:.2f}", flush=True)
    if args.trace_out:
        suffix = f".host{args.process_id}" if args.num_processes > 1 else ""
        print(f"wrote trace {args.trace_out}{suffix}", flush=True)
    art = artifact(res, args.process_id)
    n_rows = sum(len(c["rows"]) for c in art["completions"].values())
    print(f"pod_smoke host {args.process_id}/{args.num_processes}: "
          f"{art['summary']['served']} served over "
          f"{art['summary']['ticks']} ticks "
          f"({art['summary']['windows']} windows), {n_rows} owned rows",
          flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(art, f)
        print(f"wrote {args.out}", flush=True)
    print("pod_smoke OK", flush=True)


if __name__ == "__main__":
    main()
