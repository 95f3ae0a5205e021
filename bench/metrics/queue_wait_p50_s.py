"""Scheduler: median wait of a window's requests from ``queued`` to
``admitted`` (the program's request timelines).  Moves ``xc_p95_s``."""
import statistics


def read(run):
    waits = [w for j in run["jobs"] for w in j.queue_wait_s]
    return statistics.median(waits) if waits else None
