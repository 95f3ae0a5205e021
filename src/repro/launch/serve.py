"""Distributed serving launcher: batched prefill + decode service loop.

Same pjit path as the decode dry-run shapes, at configurable scale::

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --reduced \
        --devices 8 --mesh-shape 2x4 --requests 3 --batch 4 --tokens 8

Each "request wave" is a batch of prompts; the service prefills the cache
with ONE jitted ``lax.scan`` over prompt positions (identical math to the
token-by-token loop, s dispatches fused into 1) and then decodes
``--tokens`` new tokens per sequence.
"""
import argparse


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh-shape", default="")
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default="",
                    help="export a Chrome trace-event JSON with one span "
                         "per prefill/decode wave (Perfetto-loadable)")
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse_args(argv)
    from repro.launch.mesh import host_mesh
    mesh = host_mesh(args.mesh_shape, force_devices=args.devices)

    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.steps import make_ctx
    from repro.models import transformer as tf
    from repro.parallel import sharding as shd

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    d, m = mesh.shape["data"], mesh.shape["model"]
    ctx = make_ctx(mesh)
    from repro.obs import NULL_TRACER, Tracer
    tracer = Tracer(process_name="llm-serve") if args.trace_out \
        else NULL_TRACER
    print(f"serving {args.arch} on data:{d}xmodel:{m} "
          f"(window={args.window or 'full'})")

    key = jax.random.PRNGKey(args.seed)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    with jax.set_mesh(mesh):
        params = tf.init_params(key, cfg)
        p_shard = shd.to_shardings(shd.param_specs(params, ctx), mesh)
        params = jax.device_put(params, p_shard)
        decode = jax.jit(
            lambda p, c, toks, pos: tf.decode_step(
                p, c, {"tokens": toks}, pos, cfg, ctx, window=args.window))

        def prefill_fn(p, c, prompts):
            # scan the jitted decode step over prompt positions: the same
            # cache math as the per-token loop, one dispatch instead of s
            def body(c, tok_pos):
                tok, pos = tok_pos
                logits, c = tf.decode_step(p, c, {"tokens": tok}, pos, cfg,
                                           ctx, window=args.window)
                return c, logits[:, -1]
            toks = prompts.T[:, :, None]                  # (s, b, 1)
            pos = jnp.arange(prompts.shape[1], dtype=jnp.int32)
            c, logits = jax.lax.scan(body, c, (toks, pos))
            return logits[-1], c
        prefill = jax.jit(prefill_fn)

        b, s = args.batch, args.prompt_len
        max_len = s + args.tokens
        for req in range(args.requests):
            key, k_tok = jax.random.split(key)
            prompts = jax.random.randint(k_tok, (b, s), 0, cfg.vocab_size)
            cache = tf.init_cache(cfg, b, max_len, window=args.window)
            c_shard = shd.to_shardings(shd.cache_specs(cache, ctx), mesh)
            cache = jax.device_put(cache, c_shard)
            t0 = time.time()
            with tracer.span("prefill", cat="llm", request=req,
                             batch=b, prompt_len=s):
                last, cache = prefill(params, cache, prompts)
                jax.block_until_ready(last)
            t_prefill = time.time() - t0
            tok = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
            logits = last[:, None]
            out = [tok]
            t0 = time.time()
            with tracer.span("decode", cat="llm", request=req,
                             tokens=args.tokens):
                for i in range(args.tokens - 1):
                    logits, cache = decode(params, cache, tok,
                                           jnp.int32(s + i))
                    key, k_d = jax.random.split(key)
                    tok = jax.random.categorical(
                        k_d, logits[:, -1])[:, None].astype(jnp.int32)
                    out.append(tok)
                jax.block_until_ready(out[-1])
            t_dec = time.time() - t0
            assert bool(jnp.isfinite(logits).all())
            print(f"request {req}: prefill {b}x{s} {t_prefill:.2f}s | "
                  f"decode {args.tokens} toks {t_dec:.2f}s "
                  f"({args.tokens*b/max(t_dec,1e-9):.1f} tok/s)", flush=True)
    if args.trace_out:
        tracer.export(args.trace_out)
        print(f"wrote trace {args.trace_out} "
              f"({len(tracer.events())} events)")
    print("serving loop OK")


if __name__ == "__main__":
    main()
