"""latent_paged_roofline.* (%): the least time the chip could take for
the traced part's decode attention over a latent pool -- the larger of
its bytes (sum of the decoded tokens' context lengths x the cached row
[c | k_rope] x 2 B x layers: the traffic's own counts) over the HBM peak
and its FLOPs (2 x heads x (2 x kv_lora_rank + qk_rope_head_dim) a
cached token a layer) over the bf16 peak -- over the summed device time
of the kernel's events, by its name.  Which bound binds is printed.
Nothing matched gives nothing, never 0."""
from benchmark import flops, flops_moe_mla, harness, trace

PATTERN = r"latent_paged_decode_attention"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"] or not run.get("traced_context_sum"):
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_s = trace.named_sum_ns(ops, PATTERN) / 1e9
    if kernel_s <= 0:
        return None
    work = flops_moe_mla.latent_decode_work(run["config"],
                                            run["traced_context_sum"])
    least, binds = flops.roofline_seconds(*work, run["peaks"])
    harness.say(f"{run['metric']}: {binds} binds, least {least * 1e3:.2f} "
                f"ms of {kernel_s * 1e3:.2f} ms in the kernel")
    return 100.0 * least / kernel_s
