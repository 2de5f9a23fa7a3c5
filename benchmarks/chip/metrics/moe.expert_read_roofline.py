"""The held experts' reads (the fused read kernel under ``moe.experts``)
in % of their roofline: the least time of one step's forward and
backward expert reads over the expected routed rows
(``counts_mla_moe.expert_read_cost``) over that kernel time a step."""
import counts
import counts_mla_moe
import moe_scopes


def read(run):
    t = moe_scopes.kernel_s_per_step(run, "xbar_vmm")
    if t is None:
        return None
    ops, byts = counts_mla_moe.expert_read_cost(run["model"],
                                                run["batch"] * run["seq"])
    least, _ = counts.least_time(ops, byts, run["peaks"])
    return 100.0 * least / t
