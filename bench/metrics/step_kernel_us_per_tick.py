"""Fused tick kernel (``traj_masked_step``, the Pallas call inside the
scan window): its device time per tick.  XLA stages the kernel's operands
in on-chip memory before it runs, so its time is not bound by HBM and a
share of the HBM roofline would read over 100%; its own time is what a
later change to the kernel moves.  Moves ``images_per_s``."""


def read(run):
    s, counts = run.get("slice"), run.get("slice_counts")
    k = (s or {}).get("kernel", {}).get("jit_window")
    if not k or not counts or not counts["windows"]:
        return None
    ticks = counts["windows"] * run["config"]["engine"]["ticks_per_dispatch"]
    return 1e6 * k["s"] / ticks
