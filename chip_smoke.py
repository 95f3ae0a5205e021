"""Chip smoke test: serve the paper's U-Net at its published widths on a TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --chips 4           # the data-mesh path only

One process drives the chip.  The one-chip run

1. compiles the fused denoise tick (``pallas_masked``) at the served shape,
   checks that its program holds the Mosaic kernel (``tpu_custom_call``)
   and that it agrees with the jnp tick to f32 rounding;
2. serves a mixed queue through the normal launcher's engine
   (``repro.launch.serve_diffusion.build_engine``, ``--model paper``):
   T=100 cosine schedule, dense DDPM plus a strided DDIM, cuts
   {0.25, 0.5, 0.75}, request batches of 1-2, the streaming client
   finisher — and checks that the compiled scan window holds the kernel;
3. checks every disclosed x_c and final x_0 is finite, and the x_c of the
   requests of two (sampler, cut) classes against
   ``collafuse.split_sample_lane`` under the same keys.

``--chips 4`` serves the same queue on a (4, 1) data mesh and on one chip,
in the same process, and compares the two; it runs no other phase.

Weights and data are generated from ``--seed``; no file is read.  The last
line of standard output is ``{"ok": true, "device": {...}}``; any failed
check raises, so the exit code is not 0 and that line is not printed.  The
phase functions take their sizes as arguments so that a CPU test can run
them at a tiny size; :func:`main` requires a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import collafuse  # noqa: E402
from repro.core.collafuse import CutPlan  # noqa: E402
from repro.data.synthetic import (ClientDataConfig,  # noqa: E402
                                  make_client_datasets)
from repro.diffusion.backend import get_backend  # noqa: E402
from repro.diffusion.sampler import make_sampler  # noqa: E402
from repro.diffusion.schedule import cosine_schedule  # noqa: E402
from repro.launch import serve_diffusion  # noqa: E402

# The served and the reference programs run every matmul and conv at f32
# ("highest"), so the only difference between an engine lane and its
# single-lane replay is the order of f32 sums, which may depend on the
# batch a conv sees.
PRECISION = "highest"
# x_c(engine) vs x_c(split_sample_lane): the two run the same f32 ops on
# the same keys, and may differ only by rounding where a conv's summation
# order depends on its batch.  The chain amplifies such a difference at a
# few pixels: on the paper U-Net (random weights, CPU, f32), a 1-ulp change
# of x_T alone moved x_c by at most 3.1e-3 (mean 1.6e-6) after 75 dense
# DDPM steps and by 1.7e-3 (mean 8.4e-7) after 10 DDIM steps, with 5 of
# 16384 pixels past 1e-3.  A difference of an ulp at every model call can
# add up to some tens of such spreads, hence a max bound of 0.1 and a mean
# bound of 1e-4.  On a v5e the served lanes of this queue differed from
# their replay by at most 9.9e-4 (mean 4.1e-7).  A wrong key, step, column
# or lane moves x_c by O(1) at most pixels (x_c lies in [-3, 3]), far past
# both.
XC_MAX_TOL = 0.1
XC_MEAN_TOL = 1e-4
T_STEPS = 100
DDIM_STEPS = 20


def _require(ok, message: str) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(message)


def launcher_args(model: str, slots: int, requests: int, seed: int,
                  clients: int = 2, T: int = T_STEPS, image: int = 8):
    """The serve launcher's arguments for the smoke's queue: dense DDPM +
    a strided DDIM (``--mix``), cuts {0.25, 0.5, 0.75}, batches 1-2."""
    return serve_diffusion._parse_args([
        "--model", model, "--image", str(image), "--T", str(T), "--mix",
        "--sampler", "ddim", "--num-steps", str(min(DDIM_STEPS, T // 2)),
        "--slots", str(slots), "--requests", str(requests),
        "--max-batch", "2", "--clients", str(clients),
        "--finish-mode", "stream", "--seed", str(seed)])


def _tick_bound(x, c_eps, ar, ks, eps, z, out):
    """Elementwise bound on the f32 rounding of one tick: 8 ulps of every
    term of (x - c·ε)/sqrt(ar) + keep·σ·z and of the result."""
    ulp = np.finfo(np.float32).eps
    return 8 * ulp * ((np.abs(x) + np.abs(c_eps * eps)) / np.sqrt(ar)
                      + np.abs(ks * z) + np.abs(out))


def check_fused_tick(slots: int, image_size: int, seed: int, T: int = T_STEPS):
    """The fused masked tick vs the jnp tick on one slot array at the
    served shape: lanes on dense-DDPM and strided-DDIM columns, inactive
    lanes with junk columns.  Active lanes agree to f32 rounding; inactive
    lanes pass through bit-unchanged.  On a TPU the compiled tick must
    hold the Mosaic kernel."""
    sched = cosine_schedule(T)
    ddpm = make_sampler(T)
    ddim = make_sampler(T, "ddim", min(DDIM_STEPS, T // 2))
    tables = jnp.concatenate([ddpm.tables(sched), ddim.tables(sched)], 1)
    rng = np.random.default_rng(seed)
    shape = (slots, image_size, image_size, 1)
    images, _ = make_client_datasets(ClientDataConfig(
        n_clients=1, per_client=slots, image_size=image_size, holdout=1,
        seed=seed))
    # a mid-chain iterate: client images under unit noise
    x = np.asarray(images[0]) + rng.normal(0, 1, shape).astype(np.float32)
    eps = rng.normal(0, 1, shape).astype(np.float32)
    z = rng.normal(0, 1, shape).astype(np.float32)
    cols = rng.integers(0, tables.shape[1], slots).astype(np.int32)
    cols[:4] = [0, ddpm.K - 1, ddpm.K, tables.shape[1] - 1]  # both edges
    active = rng.random(slots) < 0.75
    active[:4] = True
    cols[~active] = rng.integers(-10 ** 6, 10 ** 6, int((~active).sum()))
    fused = jax.jit(get_backend("pallas_masked").masked_index_step)
    ref = jax.jit(get_backend("jnp").masked_index_step)
    args = (jnp.asarray(x), jnp.asarray(cols), jnp.asarray(eps),
            jnp.asarray(z), jnp.asarray(active), tables)
    t0 = time.perf_counter()
    compiled = fused.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    if jax.default_backend() == "tpu":
        _require("tpu_custom_call" in compiled.as_text(),
                 "the compiled fused tick holds no Mosaic kernel")
    out = np.asarray(compiled(*args))
    want = np.asarray(ref(*args))
    act = np.asarray(active)
    inactive_bitwise = bool(
        (out[~act].view(np.uint32) == x[~act].view(np.uint32)).all())
    _require(inactive_bitwise, "inactive lanes changed")
    tab = np.asarray(tables)[:, np.clip(cols, 0, tables.shape[1] - 1)]
    bcast = lambda v: v.reshape((-1, 1, 1, 1))
    bound = _tick_bound(x, bcast(tab[0]), bcast(tab[1]),
                        bcast(tab[2] * tab[3]), eps, z, want)
    diff = np.abs(out - want)
    _require((diff[act] <= bound[act]).all(),
             f"fused tick differs from the jnp tick beyond f32 rounding: "
             f"max {diff[act].max():.3e}")
    _require(np.isfinite(out).all(), "fused tick: non-finite output")
    return {"max_abs_diff": float(diff[act].max()),
            "inactive_bitwise": inactive_bitwise,
            "compile_s": compile_s}


def window_has_kernel(eng) -> float:
    """Compile the engine's k-tick scan window and, on a TPU, assert that
    it holds the Mosaic kernel (``tpu_custom_call``); returns the compile
    seconds.
    Run before the first serve, whose own compile then hits the cache."""
    state = eng._init_state()
    t0 = time.perf_counter()
    compiled = eng._tick.lower(state, eng.server_params,
                               eng._menu).compile()
    compile_s = time.perf_counter() - t0
    if jax.default_backend() == "tpu":
        _require("tpu_custom_call" in compiled.as_text(),
                 "the compiled scan window holds no Mosaic kernel")
    return compile_s


def build(model: str, slots: int, n_requests: int, seed: int,
          clients: int = 2, T: int = T_STEPS, image: int = 8, mesh=None):
    """The launcher's engine, queue and client models for the smoke's
    traffic: ``(engine, requests, client_stack)``."""
    args = launcher_args(model, slots, n_requests, seed, clients, T, image)
    eng, requests, client_stack, _ = serve_diffusion.build_engine(
        args, mesh)
    return eng, requests, client_stack


def serve(eng, requests, client_stack, repeat: int = 1):
    """Serve the queue ``repeat`` times (the first pass compiles what is
    not cached); returns the last result and the wall seconds of every
    pass."""
    walls = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        res = eng.serve(list(requests), client_stack)     # x0 on host
        walls.append(time.perf_counter() - t0)
    return res, walls


def check_finite(res) -> None:
    """Every request was served and finished, with finite x_c and x_0."""
    for rid, comp in res.completions.items():
        _require(comp.client_finished, f"request {rid} was not finished")
        _require(np.isfinite(comp.x_mid).all(),
                 f"request {rid}: non-finite x_c")
        _require(np.isfinite(comp.x0).all(),
                 f"request {rid}: non-finite x_0")


def check_against_reference(res, eng, n_classes: int):
    """|x_c| difference between the engine and
    ``collafuse.split_sample_lane`` over every lane of the requests in the
    first ``n_classes`` (sampler, cut) classes of the queue, replayed
    under the same keys, sampler and cut and the engine's step backend —
    one compiled replay per class.  Returns the (max, mean) abs
    difference, asserted within :data:`XC_MAX_TOL` / :data:`XC_MEAN_TOL`."""
    server_fn = lambda x, t: eng.apply_fn(eng.server_params, x, t)
    classes = {}
    for rid in sorted(res.completions):
        r = res.completions[rid].request
        classes.setdefault((r.sampler, r.cut_ratio), []).append(rid)
    diffs = []
    for (name, cut), rids in list(classes.items())[:n_classes]:
        plan = CutPlan(eng.sched.T, cut)
        smp = eng.samplers[name]

        def lane_xc(lane_key, plan=plan, smp=smp):
            _, x_mid = collafuse.split_sample_lane(
                eng.sched, plan, server_fn, server_fn, lane_key,
                eng.image_shape, return_intermediate=True,
                backend=eng.backend, sampler=smp)
            return x_mid

        reqs = [res.completions[rid].request for rid in rids]
        keys = jnp.stack([jax.random.fold_in(r.key, i)
                          for r in reqs for i in range(r.batch)])
        ref = np.asarray(jax.jit(jax.vmap(lane_xc))(keys))
        got = np.concatenate([res.completions[rid].x_mid for rid in rids])
        diff = np.abs(got - ref)
        print(f"x_c of requests {rids} ({name}, cut {cut}, {len(keys)} "
              f"lanes) vs split_sample_lane: max abs diff "
              f"{diff.max():.3e}, mean {diff.mean():.3e}", flush=True)
        diffs.append(diff.ravel())
    return _within_tolerance(np.concatenate(diffs), "served x_c vs "
                             "split_sample_lane")


def _within_tolerance(diff, what: str):
    """(max, mean) of |diff|, asserted within XC_MAX_TOL / XC_MEAN_TOL."""
    worst, mean = float(diff.max()), float(diff.mean())
    _require(worst <= XC_MAX_TOL and mean <= XC_MEAN_TOL,
             f"{what}: max abs diff {worst:.3e} (bound {XC_MAX_TOL:g}), "
             f"mean {mean:.3e} (bound {XC_MEAN_TOL:g})")
    return worst, mean


def compare_results(a, b):
    """(max, mean) |difference| of x_c and x_0 between two serves of one
    queue, asserted within the x_c bounds: the same lanes, with the U-Net
    batch split differently."""
    _require(sorted(a.completions) == sorted(b.completions),
             "the two serves completed different requests")
    diffs = []
    for rid, ca in a.completions.items():
        cb = b.completions[rid]
        diffs += [np.abs(ca.x_mid - cb.x_mid).ravel(),
                  np.abs(ca.x0 - cb.x0).ravel()]
    return _within_tolerance(np.concatenate(diffs), "4-chip vs 1-chip")


def _device():
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_one_chip(seed: int, model: str = "paper", T: int = T_STEPS,
                 image: int = 128) -> None:
    slots, n_requests = 32, 8
    tick = check_fused_tick(slots, image, seed, T)
    print(f"fused tick: compile {tick['compile_s']:.2f}s, max abs diff vs "
          f"jnp tick {tick['max_abs_diff']:.3e} (within f32 rounding), "
          f"inactive lanes bitwise", flush=True)
    eng, requests, client_stack = build(model, slots, n_requests, seed,
                                        T=T, image=image)
    if jax.default_backend() == "tpu":
        _require(eng.backend.name == "pallas_masked",
                 f"served step backend is {eng.backend.name}")
    print(f"scan window: compile {window_has_kernel(eng):.2f}s, holds "
          f"tpu_custom_call", flush=True)
    res, walls = serve(eng, requests, client_stack, repeat=2)
    s = res.summary
    print(f"served {s['requests']} requests ({s['images']} images) over "
          f"{s['ticks']} ticks: first pass (compile + serve) {walls[0]:.2f}s,"
          f" second pass (serve) {walls[1]:.2f}s; peak device bytes "
          f"{_peak_bytes()}", flush=True)
    check_finite(res)
    worst, mean = check_against_reference(res, eng, n_classes=2)
    print(f"x_c vs split_sample_lane: max abs diff {worst:.3e} (bound "
          f"{XC_MAX_TOL:g}), mean {mean:.3e} (bound {XC_MEAN_TOL:g})",
          flush=True)


def run_four_chips(seed: int, model: str = "paper", T: int = T_STEPS,
                   image: int = 128) -> None:
    from repro.launch.mesh import make_mesh
    devs = jax.devices()
    _require(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    slots, n_requests = 32, 8
    mesh = make_mesh((4, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        eng4, requests, client_stack = build(model, slots, n_requests,
                                             seed, T=T, image=image,
                                             mesh=mesh)
        shards = eng4._init_state()["x"].addressable_shards
        _require(len({sh.device for sh in shards}) == 4 and all(
            sh.data.shape[0] == slots // 4 for sh in shards),
            "the slot array is not split over the four chips")
        print(f"4-chip scan window: compile {window_has_kernel(eng4):.2f}s,"
              f" holds tpu_custom_call; {slots // 4} lanes per chip",
              flush=True)
        res4, walls4 = serve(eng4, requests, client_stack)
    with jax.default_device(devs[0]):
        eng1, requests, client_stack = build(model, slots, n_requests,
                                             seed, T=T, image=image)
        res1, walls1 = serve(eng1, requests, client_stack)
    check_finite(res4)
    check_finite(res1)
    worst, mean = compare_results(res4, res1)
    print(f"4-chip data mesh: {walls4[0]:.2f}s (compile + serve), 1 chip: "
          f"{walls1[0]:.2f}s (compile + serve); x_c and x_0 max abs diff "
          f"{worst:.3e} (bound {XC_MAX_TOL:g}), mean {mean:.3e} (bound "
          f"{XC_MEAN_TOL:g})", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    args = ap.parse_args(argv)
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    with jax.default_matmul_precision(PRECISION):
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    print(json.dumps({"ok": True, "device": _device()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
