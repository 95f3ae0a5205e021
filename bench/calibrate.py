"""Readings the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload paper_unet.ddpm100_job \\
        --seeds 11 12 13 [--control-seeds 11 12 13] [--out readings.jsonl]

For each seed, in one process: build the cell, serve one job through the
timed path at the cell's own sizes, draw the check's sample, and replay it
with the plain reference at the configuration's precision.  The gaps are
the program's readings (the lower ones of a limit).  For each control
seed the reference is also run at the configuration's
``control_precision`` (the next precision below) and its gaps to the
reference are the control's readings (the upper ones).  One JSON line per
seed on standard output, and in ``--out`` where given.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as harness  # noqa: E402
from benchlib import check, spec  # noqa: E402


def readings(c: dict, seed: int, control: bool) -> dict:
    import jax
    from benchlib.cell import Cell
    config, traffic = c["config"], c["traffic"]
    with jax.default_matmul_precision(config["precision"]):
        cell = Cell(config, traffic, seed)
        job = cell.run_job(1)
    sample = check.draw_sample([job.outputs], seed)
    cell.free()
    del cell
    ref = check.reference_outputs(config, traffic, sample, seed,
                                  config["precision"])
    out = {"seed": seed, "failed": job.failed, "program":
           check.gaps(sample, ref)}
    if control:
        low = check.reference_outputs(config, traffic, sample, seed,
                                      config["control_precision"])
        out["control"] = check.gaps(
            check.in_place_of_program(sample, low), ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    c = spec.cell(args.workload)
    jax = harness.configure_jax()
    if harness.device_check(jax, c["workload"]["chips"]) is None:
        return 2
    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        line = json.dumps(readings(c, seed, seed in args.control_seeds))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
