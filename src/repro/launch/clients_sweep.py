"""Multi-client round-scaling launcher — the batched CollaFuse engine on a
real (data, model) mesh.

Runs REAL collaborative rounds (not a dry-run) of the paper's U-Net at
reduced scale while sweeping ``n_clients``: client params/opt ride the mesh
as [n_clients, ...] stacks sharded client-axis-over-data, and the fused
server round generates + pools every client's upload inside ONE pjit
program whose pooled batch is sharded along ``data``.  On this CPU
container use ``--devices N`` to force N host devices::

    PYTHONPATH=src python -m repro.launch.clients_sweep --devices 4 \
        --mesh-shape 4x1 --clients 2 8 32 --rounds 3 --batch 4

On a real TPU slice, omit ``--devices`` and pass the pod's mesh shape.
``--compare-looped`` also times the per-client reference loop, printing the
batched-engine speedup per sweep point.
"""
import argparse
import json


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, nargs="+", default=[2, 8, 32])
    ap.add_argument("--rounds", type=int, default=3,
                    help="timed rounds per sweep point (after 1 warmup)")
    ap.add_argument("--batch", type=int, default=4, help="per-client batch")
    ap.add_argument("--image", type=int, default=8)
    ap.add_argument("--T", type=int, default=20)
    ap.add_argument("--cut-ratio", type=float, default=0.8)
    ap.add_argument("--step-backend", default="jnp",
                    choices=["jnp", "pallas", "pallas_masked"],
                    help="denoise-tick StepBackend used by trainer.sample")
    ap.add_argument("--sampler", default="ddpm", choices=["ddpm", "ddim"],
                    help="trajectory family trainer.sample walks (ddim "
                         "strides the chain to --num-steps)")
    ap.add_argument("--num-steps", type=int, default=0,
                    help="DDIM trajectory length K (0 = dense T steps)")
    ap.add_argument("--eta", type=float, default=0.0,
                    help="DDIM stochasticity in [0,1]")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU dry environments)")
    ap.add_argument("--mesh-shape", default="",
                    help="DxM, e.g. 4x1; default = all devices on data axis")
    ap.add_argument("--compare-looped", action="store_true",
                    help="also time the per-client reference loop")
    ap.add_argument("--json", default="",
                    help="write the sweep records to this path")
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse_args(argv)
    from repro.launch.mesh import host_mesh
    mesh = host_mesh(args.mesh_shape, force_devices=args.devices)

    import dataclasses
    import time

    import jax

    from repro.configs.base import UNetConfig
    from repro.core.trainer import CollaFuseTrainer, TrainerConfig
    from repro.models import unet

    d, m = mesh.shape["data"], mesh.shape["model"]
    print(f"clients_sweep: mesh=data:{d}xmodel:{m} batch={args.batch} "
          f"image={args.image} T={args.T} c={args.cut_ratio}")

    ucfg = dataclasses.replace(
        UNetConfig().reduced(), image_size=args.image, base_channels=8,
        channel_mults=(1, 2), n_res_blocks=1, attn_resolutions=(),
        time_dim=32, norm_groups=4)
    init_fn = lambda k: unet.init_params(k, ucfg)
    apply_fn = lambda p, x, t: unet.forward(p, x, t, ucfg)

    def data_for(n):
        ks = jax.random.split(jax.random.PRNGKey(42), n)
        return [jax.random.normal(k, (args.batch, args.image, args.image, 1))
                for k in ks]

    def timed_rounds(trainer, batches):
        trainer.train_round(batches)                      # compile + warmup
        t0 = time.perf_counter()
        for _ in range(args.rounds):
            metrics = trainer.train_round(batches)
        return (time.perf_counter() - t0) / args.rounds, metrics

    records = []
    print("n_clients,round_s,server_gflops,client_gflops,server_loss,"
          "speedup_vs_looped")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    with jax.set_mesh(mesh):
        for n in args.clients:
            cfg = TrainerConfig(n_clients=n, T=args.T,
                                cut_ratio=args.cut_ratio,
                                step_backend=args.step_backend,
                                sampler=args.sampler,
                                sampler_steps=args.num_steps, eta=args.eta)
            tr = CollaFuseTrainer(cfg, init_fn, apply_fn, mesh=mesh)
            batches = data_for(n)
            sec, metrics = timed_rounds(tr, batches)
            losses = (metrics.get("client_losses", []) +
                      [metrics[k] for k in ("server_loss",) if k in metrics])
            assert losses and all(v == v for v in losses), \
                f"NaN/absent losses: {losses}"
            # exercise the sampling seam the flags configure: split
            # inference on the chosen trajectory/backend must stay finite
            gen = tr.sample(jax.random.PRNGKey(5),
                            (2, args.image, args.image, 1))
            assert bool(jax.numpy.isfinite(gen).all()), \
                "non-finite split sample"
            speedup = None                    # null in the JSON artefact
            if args.compare_looped:
                looped = CollaFuseTrainer(
                    dataclasses.replace(cfg, batched=False),
                    init_fn, apply_fn)
                lsec, _ = timed_rounds(looped, batches)
                speedup = lsec / sec
            rec = {"n_clients": n, "round_s": sec,
                   "server_flops": metrics["server_flops"],
                   "client_flops": metrics["client_flops"],
                   "server_loss": metrics.get("server_loss"),
                   "speedup_vs_looped": speedup,
                   "mesh": f"{d}x{m}"}
            records.append(rec)
            print(f"{n},{sec:.4f},{metrics['server_flops']/1e9:.3f},"
                  f"{metrics['client_flops']/1e9:.3f},"
                  f"{metrics.get('server_loss', float('nan')):.4f},"
                  f"{speedup:.2f}" if speedup is not None else
                  f"{n},{sec:.4f},{metrics['server_flops']/1e9:.3f},"
                  f"{metrics['client_flops']/1e9:.3f},"
                  f"{metrics.get('server_loss', float('nan')):.4f},-",
                  flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.json}")
    print(f"clients sweep OK: {len(records)} points")


if __name__ == "__main__":
    main()
