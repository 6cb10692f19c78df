"""gqa_paged_roofline.* (%): bytes of K and V that the traced part's
decoded tokens needed IN THE GQA LAYERS OF THE CUT (sum of their context
lengths x kv heads x d x 2 x 2 B x the GQA layers, one in four here:
`flops_hybrid.gqa_decode_bytes`) over the HBM peak, over the summed
device time of the paged decode kernel's events, by its name.  Memory
binds: decode attention does 1 FLOP per byte.  Nothing matched gives
nothing, never 0."""
from benchmark import flops_hybrid as fh, trace

PATTERN = r"(?<!latent_)paged_decode_attention"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"] or not run.get("traced_context_sum"):
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_s = trace.named_sum_ns(ops, PATTERN) / 1e9
    if kernel_s <= 0:
        return None
    need = fh.gqa_decode_bytes(run["config"], run["traced_context_sum"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / kernel_s
