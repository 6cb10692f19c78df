"""paged_roofline.* (%): bytes of K and V that the traced part's decoded
tokens needed (sum of their context lengths x kv heads x d x 2 x 2 B x
layers: the traffic's own counts) over the HBM peak, over the summed
device time of the paged decode kernel's events.  Memory binds: decode
attention does 1 FLOP per byte.

PATTERN is read off a trace by hand (no stable kernel name yet; PERF.md,
Open questions).  Nothing matched gives nothing, never 0."""
from benchmark import flops, trace

PATTERN = r"paged_decode|paged_attention"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"] or not run.get("traced_context_sum"):
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_s = trace.named_sum_ns(ops, PATTERN) / 1e9
    if kernel_s <= 0:
        return None
    need = flops.paged_decode_bytes(run["config"],
                                    run["traced_context_sum"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / kernel_s
