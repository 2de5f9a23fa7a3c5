"""Rank-k write kernel (``kernels/xbar_update``) in % of its roofline:
the least time one step's writes need (``counts.write_cost``) over the
kernel's device time per step in the trace."""
import counts


def read(run):
    t = run["trace"]["kernels"].get("xbar_update", 0.0)
    if t <= 0.0:
        return None
    ops, byts = counts.write_cost(run["model"], run["batch"] * run["seq"],
                                  run["pulse_train"])
    least, _ = counts.least_time(ops, byts, run["peaks"])
    return 100.0 * least * run["steps"] / t
