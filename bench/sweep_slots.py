"""Per-image time of a configuration's model forward (through its family
adapter, ``bench/families/``) at several batch sizes, on the chip: once
it stops falling, more slots stop raising images/s and only lengthen
each job.

    python3 bench/sweep_slots.py --config paper_unet --batches 8 32 64 128

One JSON line per batch on standard output: the batch, the seconds per
image (median of three timings, each of enough calls to span half a
second), the compile seconds, and the compiled program's argument, output
and temporary bytes.  Weights and inputs (and class labels, on a
conditional model) are drawn from ``--seed``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as harness  # noqa: E402
from benchlib import spec  # noqa: E402


def per_image_s(fn, args, batch: int) -> float:
    times = []
    for _ in range(3):
        n, t0 = 0, time.perf_counter()
        while True:
            out = fn(*args)
            n += 1
            if n >= 2 and time.perf_counter() - t0 >= 0.5:
                break
        out.block_until_ready()
        times.append((time.perf_counter() - t0) / (n * batch))
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--batches", type=int, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    config = spec.load_json(BENCH / "configs" / f"{args.config}.json")
    jax = harness.configure_jax()
    if harness.device_check(jax, 1) is None:
        return 2
    import jax.numpy as jnp

    m = config["model"]
    family = spec.family(config)
    nc = family.num_classes(m)
    T = config["schedule"]["T"]
    with jax.default_matmul_precision(config["precision"]):
        key = jax.random.PRNGKey(args.seed)
        params = jax.jit(family.init(m))(key)
        fwd = jax.jit(family.apply_fn(m))
        for b in args.batches:
            kx, kt = jax.random.split(jax.random.fold_in(key, b))
            x = jax.random.normal(kx, (b,) + tuple(family.image_shape(m)),
                                  jnp.float32)
            t = jax.random.randint(kt, (b,), 1, T + 1)
            inputs = (params, x, t)
            if nc:
                inputs += (jax.random.randint(jax.random.fold_in(kt, 1),
                                              (b,), 0, nc + 1),)
            t0 = time.perf_counter()
            compiled = fwd.lower(*inputs).compile()
            compile_s = time.perf_counter() - t0
            mem = compiled.memory_analysis()
            print(json.dumps({
                "config": args.config, "batch": b,
                "s_per_image": per_image_s(compiled, inputs, b),
                "compile_s": compile_s,
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
