"""sparse_prefill_roofline.* (%): the least time the chip could take for
the traced chunks' attention over each query's own picks, over the
summed device time of the kernel's events, by its name
(``sparse_prefill_attention``).  Work (`flops_keye.sparse_prefill_work`),
from the traced chunks' own spans, in every layer but the last (a
prefill program prunes it): ``4 x 32 x 128`` FLOPs a SELECTED pair
(``selected_pairs``, at most the top-k a query), K and V of what a
chunk's queries see read once, q and o of its queries; the larger of
FLOPs over the bf16 peak and bytes over the HBM peak, which binds is
printed.  The kernel computes every visible pair and tests each against
the query's threshold, so the share reads low where the picks are a
small part of what a query sees: honestly, the pairs beyond the picks
are no work of the model.  A share over 100% is refused (nothing is
reported).  Nothing matched, or spans with no such counts, gives
nothing, never 0."""
from benchmark import flops, flops_keye as fk, harness, trace
from benchmark import program_spans as ps

PATTERN = r"sparse_prefill_attention"


def read(run):
    tr, got = run.get("trace"), ps.serving(run)
    if not tr or not tr["devices"] or got is None:
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_s = trace.named_sum_ns(ops, PATTERN) / 1e9
    first = got["first_traced"]
    s = fk.span_sums(got["steps"][first:first + got["n_traced"]])
    if kernel_s <= 0 or not s.get("selected_pairs"):
        return None
    layers = int(run["config"]["num_hidden_layers"]) - 1
    least, binds = flops.roofline_seconds(*fk.sparse_prefill_work(
        run["config"], layers * s["selected_pairs"], layers * s["seen"],
        layers * s["tokens"]), run["peaks"])
    harness.say(f"{run['metric']}: {binds} binds, least {least * 1e3:.2f} "
                f"ms of {kernel_s * 1e3:.2f} ms in the kernel; the kernel "
                f"computed {s.get('attended_pairs', 0)} pairs a layer for "
                f"{s['selected_pairs']} selected")
    share = 100.0 * least / kernel_s
    if share > 100.0:
        harness.say(f"{run['metric']}: REFUSED, {share:.1f}% of the "
                    f"roofline: a count too high or a time too short")
        return None
    return share
