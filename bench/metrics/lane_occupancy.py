"""Scheduler: share of slot-ticks that carried a live lane, from the
engine's per-tick occupancy (``utilization_mean``), weighted by each
window job's ticks.  Moves ``images_per_s``."""


def read(run):
    ticks = sum(j.ticks for j in run["jobs"])
    if not ticks:
        return None
    return 100.0 * sum(j.utilization_mean * j.ticks
                       for j in run["jobs"]) / ticks
