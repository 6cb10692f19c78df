"""train_mfu (%): model FLOPs per token from the configuration's shapes
x tokens per second of the run's untraced window, over the chips' bf16
peak.  Source: host clock + shapes (no trace needed)."""
from benchmark import flops


def read(run):
    if not run.get("tokens_per_s"):
        return None
    per_token = flops.train_flops_per_token(run["config"],
                                            int(run["mix"]["seq"]))
    return 100.0 * flops.mfu(per_token * run["tokens_per_s"], run["chips"],
                             run["peaks"]["bf16_flops"])
