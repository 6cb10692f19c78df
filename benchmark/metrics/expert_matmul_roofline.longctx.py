"""expert_matmul_roofline.longctx (%): `expert_matmul_roofline`'s rule
for a configuration whose shapes `flops_moe_mla` cannot read (no latent
attention): the least time the chip could take for the routed experts'
grouped products of the traced steps (`flops_keye.expert_work`: 2 FLOPs
per weight of one expert an assignment, the weights of the experts
TOUCHED and the rows, all from the traced steps' own spans: a decode
step's ``moe_assignments`` / ``experts_touched`` and its chunks'
``prefill_*`` twins) over the summed device time of the grouped-matmul
kernel's events (`gmm`).  Nothing matched gives nothing, never 0."""
from benchmark import flops, flops_keye as fk, harness, trace
from benchmark import program_spans as ps

PATTERN = r"\bgmm\b"


def read(run):
    tr, got = run.get("trace"), ps.serving(run)
    if not tr or not tr["devices"] or got is None:
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_s = trace.named_sum_ns(ops, PATTERN) / 1e9
    first = got["first_traced"]
    assignments = touched = 0.0
    for root, _ in got["steps"][first:first + got["n_traced"]]:
        counts = root[ps.COUNTS]
        assignments += counts.get("moe_assignments", 0) \
            + counts.get("prefill_moe_assignments", 0)
        touched += counts.get("experts_touched", 0) \
            + counts.get("prefill_experts_touched", 0)
    if kernel_s <= 0 or not assignments:
        return None
    least, binds = flops.roofline_seconds(
        *fk.expert_work(run["config"], assignments, touched), run["peaks"])
    harness.say(f"{run['metric']}: {binds} binds, least {least * 1e3:.2f} "
                f"ms of {kernel_s * 1e3:.2f} ms in the kernel")
    return 100.0 * least / kernel_s
