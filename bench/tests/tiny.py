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


def config(schedule="cosine", T=20):
    return {"name": "tiny", "reference": "unet_ddpm", "model": dict(MODEL),
            "schedule": {"name": schedule, "T": T}, "precision": "highest",
            "control_precision": "high",
            "engine": {"slots": 8, "clients": 2, "ticks_per_dispatch": 1,
                       "async_depth": 1, "finish_async_depth": 1,
                       "clip": 3.0}}


def cell(traffic="ddpm100_job", limits_of="paper_unet.ddpm100_job", **kw):
    """The pieces of a tiny cell, held to the limits of a real one."""
    b = spec.benchmark()
    return {"workload": {"name": "tiny", "chips": 1},
            "config": config(**kw),
            "traffic": spec.load_json(BENCH / "traffic" / f"{traffic}.json"),
            "limits": spec.load_json(BENCH / "checks" / f"{limits_of}.json"),
            "end_to_end": b["end_to_end"], "per_layer": b["per_layer"]}


def cpu_cache():
    """Keep the harness's compile cache of CPU runs out of the checkout,
    where a chip run would find entries it cannot load."""
    import tempfile
    import run as harness
    harness.CACHE_DIR = Path(tempfile.mkdtemp(prefix="bench_cpu_cache_"))


def args(seed=3_000_000_019, seconds=0.5, trace=0):
    return argparse.Namespace(workload="tiny", seed=seed, seconds=seconds,
                              trace=trace)
