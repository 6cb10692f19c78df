"""expert_matmul_roofline.* (%): the least time the chip could take for
the routed experts' grouped products of the traced steps, over the
summed device time of the grouped-matmul kernel's events, by its name.

Work, from the traced steps' own spans, as the programs count it
themselves (the routed layers' own load, returned by both programs): an
ASSIGNMENT is one token sent to one expert (a decode step's
``moe_assignments``, its chunks' ``prefill_moe_assignments``) and costs
2 FLOPs per weight of one expert (three products) and the rows it reads
and writes; the weights read are those of the experts TOUCHED
(``experts_touched``, ``prefill_experts_touched``).  A chunk counts the
layers whose products run: its last layer's output is dead code in a
prefill program.  A program that counts no chunks (the counts are
younger than the kernel) leaves them out: the share is then understated,
never overstated.
The larger of FLOPs over the bf16 peak and bytes over the HBM peak;
which binds is printed.  Nothing matched gives nothing, never 0."""
from benchmark import flops, flops_moe_mla as fm, harness, trace
from benchmark import program_spans as ps

PATTERN = r"\bgmm\b"


def work(cfg, steps, itemsize=2):
    """(flops, bytes) of the grouped products of `steps`
    [(root, children)]; (0, 0) where no step carries the counts."""
    assignments = touched = 0.0
    for root, _ in steps:
        counts = root[ps.COUNTS]
        assignments += counts.get("moe_assignments", 0) \
            + counts.get("prefill_moe_assignments", 0)
        touched += counts.get("experts_touched", 0) \
            + counts.get("prefill_experts_touched", 0)
    rows = 3 * (int(cfg["hidden_size"]) + int(cfg["moe_intermediate_size"]))
    return (2.0 * fm.expert_params(cfg) * assignments,
            fm.touched_expert_bytes(cfg, touched, itemsize)
            + assignments * rows * itemsize)


def read(run):
    tr, got = run.get("trace"), ps.serving(run)
    if not tr or not tr["devices"] or got is None:
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_s = trace.named_sum_ns(ops, PATTERN) / 1e9
    first = got["first_traced"]
    need = work(run["config"], got["steps"][first:first + got["n_traced"]])
    if kernel_s <= 0 or not need[0]:
        return None
    least, binds = flops.roofline_seconds(*need, run["peaks"])
    harness.say(f"{run['metric']}: {binds} binds, least {least * 1e3:.2f} "
                f"ms of {kernel_s * 1e3:.2f} ms in the kernel")
    return 100.0 * least / kernel_s
