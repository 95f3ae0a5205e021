"""Whole step on the device: useful model operations per second over the
chip's peak.  Useful are the lane-steps the traced job asks for, on the
server and on the clients (from its composition), at the benchmark's own
count per forward; idle lanes and padding do not count.  Moves
``images_per_s``."""


def read(run):
    s, counts = run.get("slice"), run.get("slice_counts")
    if not s or not counts or not s.get("window_s"):
        return None
    steps = counts["server_lane_steps"] + counts["finish_lane_steps"]
    if not steps:
        return None
    flops = steps * run["flops_per_forward"]
    return 100.0 * flops / s["window_s"] / run["peaks"]["flops"]
