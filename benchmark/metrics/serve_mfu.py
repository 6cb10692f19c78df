"""serve_mfu.* (%): 2 FLOPs per matmul weight per token the engine pushed
through the model (prefilled + decoded, from ``step()``'s own summaries)
in the untraced part of the window, over that time and the chip's bf16
peak.  The whole serving step's share of the peak."""
from benchmark import flops


def read(run):
    if not run.get("tokens_processed") or not run.get("quiet_s"):
        return None
    rate = flops.serve_flops(run["config"], run["tokens_processed"]) \
        / run["quiet_s"]
    return 100.0 * flops.mfu(rate, run["chips"],
                             run["peaks"]["bf16_flops"])
