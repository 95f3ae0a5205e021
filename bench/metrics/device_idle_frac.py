"""Engine host loop: share of the traced slice in which no operation ran
on the device (1 - union of device-op intervals / slice length).  Moves
``images_per_s``."""


def read(run):
    s = run.get("slice")
    if not s or not s.get("window_s"):
        return None
    return 1.0 - s["busy_s"] / s["window_s"]
