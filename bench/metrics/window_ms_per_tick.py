"""Scan window: device time of the engine's window program
(``jit(window)``: the U-Net on every slot and the fused tick) per tick
dispatched in the traced slice.  Moves ``images_per_s``."""
from benchlib.trace import module_time


def read(run):
    s, counts = run.get("slice"), run.get("slice_counts")
    if not s or not counts or not counts["windows"]:
        return None
    t = module_time(s, "jit_window")
    if t is None:
        return None
    ticks = counts["windows"] * run["config"]["engine"]["ticks_per_dispatch"]
    return 1e3 * t / ticks
