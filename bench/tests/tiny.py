"""A tiny cell for the harness's own tests on the CPU: the U-Net of the
configurations at toy widths, driven through the whole run."""
import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import spec  # noqa: E402

MODEL = {"image_size": 16, "in_channels": 1, "base_channels": 8,
         "channel_mults": [1, 2], "n_res_blocks": 1, "attn_resolutions": [8],
         "time_dim": 16, "norm_groups": 4, "dtype": "float32"}


def config(schedule="cosine", T=20, num_classes=0):
    model = dict(MODEL, num_classes=num_classes) if num_classes else \
        dict(MODEL)
    return {"name": "tiny", "reference": "unet_ddpm", "model": model,
            "schedule": {"name": schedule, "T": T}, "precision": "highest",
            "control_precision": "high",
            "engine": {"slots": 8, "clients": 2, "ticks_per_dispatch": 1,
                       "async_depth": 1, "finish_async_depth": 1,
                       "clip": 3.0}}


def cell(traffic="ddpm100_job", limits_of="paper_unet.ddpm100_job", **kw):
    """The pieces of a tiny cell, held to the limits of a real one; its
    traffic is a mix of ``bench/traffic/`` by name, or a mix itself."""
    b = spec.benchmark()
    if isinstance(traffic, str):
        traffic = spec.load_json(BENCH / "traffic" / f"{traffic}.json")
    return {"workload": {"name": "tiny", "chips": 1},
            "config": config(**kw), "traffic": traffic,
            "limits": spec.load_json(BENCH / "checks" / f"{limits_of}.json"),
            "end_to_end": b["end_to_end"], "per_layer": b["per_layer"]}


# half DDIM-20 unguided and half DDIM-20 at guidance w = 1.5 (a cond and
# an uncond lane an image on the server), cuts .25/.5/.75, batch 1-2
GUIDED_MIX = {
    "samplers": {
        "ddim20": {"family": "ddim", "num_steps": 20, "eta": 0.0},
        "ddim20_cfg": {"family": "ddim", "num_steps": 20, "eta": 0.0,
                       "guidance": 1.5}},
    "cut_ratios": [0.25, 0.5, 0.75],
    "batch": {"min": 1, "max": 2},
    "job_images_per_slot": 2,
    "scheduler": {"policy": "cut_ratio", "pack": True}}


def guided_cell():
    """A tiny class-conditional cell (10 classes) under ``GUIDED_MIX``."""
    return cell(traffic=GUIDED_MIX, limits_of="paper_unet.ddim20_job",
                schedule="linear", T=40, num_classes=10)


def cpu_cache():
    """Keep the harness's compile cache of CPU runs out of the checkout,
    where a chip run would find entries it cannot load."""
    import tempfile
    import run as harness
    harness.CACHE_DIR = Path(tempfile.mkdtemp(prefix="bench_cpu_cache_"))


def args(seed=3_000_000_019, seconds=0.5, trace=0):
    return argparse.Namespace(workload="tiny", seed=seed, seconds=seconds,
                              trace=trace)
