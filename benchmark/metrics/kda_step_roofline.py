"""kda_step_roofline.* (%): the least time the chip could take for the
decode steps' gated delta-rule recurrence over the summed device time of
the kernel's events, by its name (`kda_decode_step`).

Work, from the TRACED steps' own spans: a live decode row
(``state_slots_live`` on the ``serving.step`` root) reads and writes its
float32 state once in every KDA layer and the vectors beside it
(`flops_hybrid.kda_step_work`); 7 FLOPs a state element.  Memory binds.
A dead row's slot, which the kernel also passes through, counts as no
work: the share is understated by it, never overstated.  Nothing matched
gives nothing, never 0."""
from benchmark import flops, flops_hybrid as fh, harness, trace
from benchmark import program_spans as ps

PATTERN = r"kda_decode_step"


def read(run):
    tr, got = run.get("trace"), ps.serving(run)
    if not tr or not tr["devices"] or got is None:
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_s = trace.named_sum_ns(ops, PATTERN) / 1e9
    first = got["first_traced"]
    rows = sum(root[ps.COUNTS].get("state_slots_live", 0) for root, _ in
               got["steps"][first:first + got["n_traced"]])
    if kernel_s <= 0 or not rows:
        return None
    least, binds = flops.roofline_seconds(
        *fh.kda_step_work(run["config"], rows), run["peaks"])
    harness.say(f"{run['metric']}: {binds} binds, least {least * 1e3:.2f} "
                f"ms of {kernel_s * 1e3:.2f} ms in the kernel")
    return 100.0 * least / kernel_s
