"""flash_bwd_ms_per_step (ms): summed device time of the two backward
flash kernels' events (``flash_attention_bwd_dkv`` and ``..._dq``, the
kernels' own names) over the traced steps.  Nothing matched gives
nothing, never 0."""
from benchmark import trace

PATTERN = r"flash_attention_bwd"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"] or not run.get("traced_steps"):
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_ns = trace.named_sum_ns(ops, PATTERN)
    return kernel_ns / 1e6 / run["traced_steps"] if kernel_ns > 0 else None
