"""Model FLOP/s utilisation of the DeepSeek-V2 analog train step, in % of
the chip's peak: model operations per token (``counts_mla_moe.
ops_per_token``: unpadded, the held experts at their expected share of
the tokens, no recomputation) times the traced window's tokens per
second."""
import counts_mla_moe


def read(run):
    if not run.get("steps") or "kv_lora_rank" not in run["model"]:
        return None
    ops = counts_mla_moe.ops_per_token(run["model"], run["seq"])
    return 100.0 * ops * run["tokens_per_s"] / run["peaks"]["flops_per_s"]
