"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload paper_unet.ddpm100_job --seed 7 \\
        --seconds 30 --trace 0

A cell is one entry of BENCHMARK.json's ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The run

1. builds the engine through the configuration's family adapter
   (``bench/families/<family>.py``) and ``EngineConfig``/``ServeEngine``,
   with weights drawn from ``--seed`` on the device, and serves one warm-up
   job, which compiles or loads every program the window uses; its
   finish programs step no lane (set-up);
2. serves whole jobs back to back until ``--seconds`` have passed (the
   window); a compile inside the window fails the run;
3. with ``--trace 1``, profiles the whole first window job and reports
   the per-layer metrics instead of the end-to-end ones;
4. replays a sample of the window's requests with the plain reference and
   holds the gaps against the cell's limits (``correct``).

The last line of standard output is one JSON object; the compared
numbers and their limits are also the last lines of standard error.
Without a TPU, or without a chip whose peaks are known, it prints no
result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".tpu_logs"))

from benchlib import spec  # noqa: E402

CACHE_DIR = ROOT / ".bench_cache"
PROFILE_DIR = ROOT / ".bench_profile"


def log(msg: str) -> None:
    print(msg, flush=True)


def configure_jax():
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def device_check(jax, chips: int):
    """The devices, or None (with the reason on stderr) where this is no
    TPU host with enough chips of a known kind."""
    from benchlib.yardsticks import PEAKS
    devs = jax.devices()
    why = None
    if devs[0].platform != "tpu":
        why = f"needs a TPU; JAX found {devs[0].platform!r}"
    elif len(devs) < chips:
        why = f"the cell needs {chips} chips; JAX found {len(devs)}"
    elif devs[0].device_kind not in PEAKS:
        why = f"no published peaks for {devs[0].device_kind!r}"
    if why:
        print(f"bench/run.py: {why}", file=sys.stderr)
        return None
    return devs


class TracedJob:
    """Profiles one whole job with ``jax.profiler``, with the device
    drained at both ends so that the dispatches counted in between are the
    ones the trace holds."""

    def __init__(self, jax, cell):
        self.jax, self.cell = jax, cell
        self._barrier = jax.jit(lambda x: x + 1)

    def drain(self):
        self.jax.block_until_ready(self._barrier(self.jax.numpy.zeros(())))

    def run_job(self, job: int):
        self.drain()
        w0 = self.cell.probe.windows
        shutil.rmtree(PROFILE_DIR, ignore_errors=True)
        self.jax.profiler.start_trace(str(PROFILE_DIR))
        try:
            with self.jax.profiler.TraceAnnotation("bench:slice"):
                rec = self.cell.run_job(job)
                self.drain()
        finally:
            self.jax.profiler.stop_trace()
        server, client = self.cell.lane_steps
        self.counts = {"windows": self.cell.probe.windows - w0,
                       "server_lane_steps": server,
                       "finish_lane_steps": client}
        return rec


def run(args, c=None, require_chip: bool = True) -> dict:
    """One run of a cell.  ``c`` (the cell's pieces, as
    :func:`spec.cell` gives them) and ``require_chip=False`` are for the
    harness's own tests, which drive a tiny cell on the CPU."""
    c = c or spec.cell(args.workload)
    config, traffic = c["config"], c["traffic"]
    jax = configure_jax()
    if require_chip:
        devs = device_check(jax, c["workload"]["chips"])
        if devs is None:
            raise SystemExit(2)
    else:
        devs = jax.devices()
    from benchlib import check, trace
    from benchlib.cell import Cell, CompileCounter, p95, peak_bytes
    from benchlib.yardsticks import peaks

    counter = CompileCounter()
    traced = bool(args.trace)
    precision = config["precision"]
    with jax.default_matmul_precision(precision):
        cell = Cell(config, traffic, args.seed, traced=traced)
        sl = TracedJob(jax, cell) if traced else None
        if sl is not None:
            sl.drain()                # compiles the barrier in set-up
        cell.warm_up()
    setup_s = time.perf_counter() - T_START
    s0 = counter.snapshot()
    log(f"set-up {setup_s:.3f} s: {s0['compiles']} programs compiled or "
        f"loaded, {s0['hits']} from the compile cache, {s0['writes']} "
        f"written to it; finisher shapes warmed "
        f"{sorted(cell.probe.finish_shapes)}")
    log(f"job: {len(cell.specs)} requests, {cell.images_per_job} images, "
        f"{cell.slots} slots, {cell.n_clients} clients")

    jobs = []
    t0 = time.perf_counter()
    with jax.default_matmul_precision(precision):
        while True:
            j = len(jobs) + 1
            jobs.append(sl.run_job(j) if sl is not None and j == 1
                        else cell.run_job(j))
            if time.perf_counter() - t0 >= args.seconds:
                break
    window_s = time.perf_counter() - t0
    in_window = counter.compiles - s0["compiles"]
    if in_window:
        raise RuntimeError(f"{in_window} programs compiled or loaded "
                           "inside the measured window")
    peak = peak_bytes()
    attempted = sum(j.attempted for j in jobs)
    failed = sum(j.failed for j in jobs)
    images = sum(j.images for j in jobs)
    log(f"window {window_s:.3f} s: {len(jobs)} jobs, {attempted} requests, "
        f"{images} images, {sum(j.ticks for j in jobs)} ticks, {failed} "
        f"failed; peak device bytes {peak}")

    sample = check.draw_sample([j.outputs for j in jobs], args.seed)
    cell.free()
    del cell
    for j in jobs:
        j.outputs = None

    reduced = None
    if traced:
        reduced = trace.reduce(trace.extract(str(PROFILE_DIR)))
    t_ref = time.perf_counter()
    ref_out = check.reference_outputs(config, traffic, sample, args.seed,
                                      precision)
    readings = check.gaps(sample, ref_out)
    checks = check.judge(readings, c["limits"])
    s1 = counter.snapshot()
    log(f"reference: {len(ref_out)} images of {len(sample)} requests "
        f"replayed in {time.perf_counter() - t_ref:.3f} s; "
        f"{s1['compiles'] - s0['compiles']} programs compiled or loaded, "
        f"{s1['hits'] - s0['hits']} from the compile cache")
    correct = check.passed(checks) and failed == 0 and attempted > 0

    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    metrics = {}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if not traced:
        values = {
            "images_per_s": images / window_s,
            "xc_p95_s": p95([x for j in jobs for x in j.xc_s]),
            "x0_p95_s": p95([x for j in jobs for x in j.x0_s]),
            "setup_s": setup_s,
        }
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        flops_per_forward = spec.family(config).forward_flops(
            config["model"])
        data = {"config": config, "traffic": traffic, "jobs": jobs,
                "slice": reduced, "slice_counts": sl.counts,
                "flops_per_forward": flops_per_forward,
                "peaks": peaks(d.device_kind)}
        for m in c["per_layer"]:
            v = spec.reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        log(f"traced job: {json.dumps(sl.counts)}; flops_per_forward "
            f"{flops_per_forward}; programs "
            f"{json.dumps((reduced or {}).get('module_s'))}; kernel "
            f"{json.dumps((reduced or {}).get('kernel'))}")
        log(f"window metrics under tracing: images/s {images / window_s}, "
            f"median x0 latency "
            f"{statistics.median([x for j in jobs for x in j.x0_s])}")
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except SystemExit as e:
        return e.code if isinstance(e.code, int) and e.code else 1
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
