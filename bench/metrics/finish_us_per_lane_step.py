"""Client finisher: device time of the finish program (``jit(finish)``)
per client lane-step the traced job asks for (from its composition);
padding lanes and steps past a lane's end count as cost, not as work.
Moves ``images_per_s``."""
from benchlib.trace import module_time


def read(run):
    s, counts = run.get("slice"), run.get("slice_counts")
    if not s or not counts or not counts["finish_lane_steps"]:
        return None
    t = module_time(s, "jit_finish")
    if t is None:
        return None
    return 1e6 * t / counts["finish_lane_steps"]
