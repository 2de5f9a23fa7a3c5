"""Fused read kernel (``kernels/xbar_vmm``) in training, in % of its
roofline: the least time one step's reads need (one VMM and one MVM of
every matrix, ``counts.read_cost``) over the kernel's device time per
step in the trace."""
import counts


def read(run):
    t = run["trace"]["kernels"].get("xbar_vmm", 0.0)
    if t <= 0.0:
        return None
    ops, byts = counts.read_cost(run["model"], run["batch"] * run["seq"])
    least, _ = counts.least_time(ops, byts, run["peaks"])
    return 100.0 * least * run["steps"] / t
