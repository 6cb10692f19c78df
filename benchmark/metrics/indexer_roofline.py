"""indexer_roofline.* (%): the least time the chip could take for the
traced steps' lightning-indexer scores over the summed device time of
the indexer kernels' events, by their names (``indexer_decode_scores``,
``indexer_prefill_scores``).  Work (`flops_keye.indexer_work`), from the
traced steps' own spans: a decode step's ``indexer_positions`` in every
layer and a chunk's ``scored_pairs`` in every layer but the last (a
prefill program prunes it), ``2 x 16 x 64`` FLOPs a pair; a decode
row's keys read once (64 bf16 values a position: the model's key, not
the pool's 128 padded lanes), a chunk's keys once a chunk.  Decode and
chunk each take the larger of FLOPs over the bf16 peak and bytes over
the HBM peak; what binds is printed.  A share over 100% means a count
too high or a time too short: it is refused (nothing is reported).
Nothing matched, or a program whose spans carry no such counts, gives
nothing, never 0."""
from benchmark import flops, flops_keye as fk, harness, trace
from benchmark import program_spans as ps

PATTERN = r"indexer_(decode|prefill)_scores"


def read(run):
    tr, got = run.get("trace"), ps.serving(run)
    if not tr or not tr["devices"] or got is None:
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_s = trace.named_sum_ns(ops, PATTERN) / 1e9
    first = got["first_traced"]
    s = fk.span_sums(got["steps"][first:first + got["n_traced"]])
    if kernel_s <= 0 or "indexer_positions" not in s:
        return None
    cfg, layers = run["config"], int(run["config"]["num_hidden_layers"])
    n, m = layers * s["indexer_positions"], layers - 1
    decode = flops.roofline_seconds(*fk.indexer_work(
        cfg, n, n, layers * s.get("decode_rows", 0)), run["peaks"])
    chunk = flops.roofline_seconds(*fk.indexer_work(
        cfg, m * s.get("scored_pairs", 0), m * s.get("seen", 0),
        m * s.get("tokens", 0)), run["peaks"])
    least = decode[0] + chunk[0]
    harness.say(f"{run['metric']}: decode {decode[1]} binds, chunks "
                f"{chunk[1]}; least {least * 1e3:.2f} ms of "
                f"{kernel_s * 1e3:.2f} ms in the kernels")
    share = 100.0 * least / kernel_s
    if share > 100.0:
        harness.say(f"{run['metric']}: REFUSED, {share:.1f}% of the "
                    f"roofline: a count too high or a time too short")
        return None
    return share
