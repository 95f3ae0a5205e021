"""Distributed LM training launcher.

Runs REAL training steps (not a dry-run) of any assigned architecture on
whatever devices exist. On this CPU container use ``--devices N`` to force N
host devices and exercise the same pjit path the production mesh uses::

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
        --devices 8 --mesh-shape 2x4 --steps 20 --batch 8 --seq 64

On a real TPU slice, omit ``--devices`` and pass the pod's mesh shape.
The training step, sharding rules, optimizer, data pipeline, and
checkpointing are the production code paths (launch/steps.py,
parallel/sharding.py, optim/, checkpoint/).
"""
import argparse


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU dry environments)")
    ap.add_argument("--mesh-shape", default="",
                    help="DxM, e.g. 2x4; default = all devices on data axis")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--ckpt", default="",
                    help="save final params+opt to this .npz path")
    ap.add_argument("--resume", default="", help="restore from .npz path")
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse_args(argv)
    from repro.launch.mesh import host_mesh
    mesh = host_mesh(args.mesh_shape, force_devices=args.devices)

    import time

    import jax

    from repro.checkpoint import io as ckpt_io
    from repro.configs import get_config
    from repro.data.synthetic import token_batches
    from repro.launch.steps import make_ctx, make_train_step
    from repro.models import transformer as tf
    from repro.optim import adamw
    from repro.parallel import sharding as shd

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    d, m = mesh.shape["data"], mesh.shape["model"]
    ctx = make_ctx(mesh)
    print(f"arch={args.arch} reduced={args.reduced} mesh=data:{d}xmodel:{m} "
          f"fsdp={args.fsdp}")

    key = jax.random.PRNGKey(0)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    with jax.set_mesh(mesh):
        params = tf.init_params(key, cfg)
        opt_cfg = adamw.AdamWConfig(lr=args.lr)
        opt = adamw.init_state(params, opt_cfg)
        if args.resume:
            params = ckpt_io.restore_checkpoint(args.resume, params)
            print(f"restored params from {args.resume}")
        # place according to the production sharding rules
        p_spec = shd.param_specs(params, ctx, fsdp=args.fsdp)
        p_shard = shd.to_shardings(p_spec, mesh)
        params = jax.device_put(params, p_shard)
        o_spec = {"step": jax.sharding.PartitionSpec(), "mu": p_spec,
                  "nu": p_spec}
        opt = jax.device_put(opt, shd.to_shardings(o_spec, mesh))

        step_fn = jax.jit(make_train_step(cfg, ctx, opt_cfg,
                                          remat=args.remat),
                          donate_argnums=(0, 1))
        data = token_batches(cfg.vocab_size, args.batch, args.seq)
        b_spec = shd.batch_specs(
            jax.tree.map(lambda x: x, next(data)), ctx)
        b_shard = shd.to_shardings(b_spec, mesh)

        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"params: {n/1e6:.1f}M; starting {args.steps} steps")
        t0 = time.time()
        losses = []
        for i in range(args.steps):
            batch = jax.device_put(next(data), b_shard)
            params, opt, metrics = step_fn(params, opt, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                loss = float(metrics["loss"])
                losses.append(loss)
                print(f"step {i:4d} loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"({(time.time()-t0)/(i+1):.2f}s/step)", flush=True)
        assert losses[-1] < losses[0], \
            f"loss did not improve: {losses[0]} -> {losses[-1]}"
        if args.ckpt:
            ckpt_io.save_checkpoint(args.ckpt, jax.device_get(params),
                                    step=args.steps)
            print(f"saved {args.ckpt}")
        print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
              f"in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
