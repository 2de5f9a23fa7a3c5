"""Model FLOP/s utilisation of the analog train step, in % of the chip's
peak: model operations per token (``counts.ops_per_token``: unpadded,
no recomputation) times the traced window's tokens per second."""
import counts


def read(run):
    if not run.get("steps"):
        return None
    ops = counts.ops_per_token(run["model"], run["seq"])
    return 100.0 * ops * run["tokens_per_s"] / run["peaks"]["flops_per_s"]
