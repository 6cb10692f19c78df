"""sparse_serve_mfu.* (%): model FLOPs of the quiet steps of a GQA decoder
whose queries attend only what a learned indexer picks, over their time
and the chip's bf16 peak: the whole serving step's share of the peak.
`flops_keye.serve_flops`: 2 FLOPs per matmul weight a token REALLY
multiplies -- attention and indexer projections, the router, the
experts by the programs' own load (``moe_assignments`` +
``prefill_moe_assignments``), the head for decoded tokens -- and the
pairs: ``2 x 16 x 64`` FLOPs a scored pair and ``4 x 32 x 128`` a
SELECTED pair a layer.  A decode step's pairs are its root's
``indexer_positions`` and ``selected_positions``, a chunk's its span's
``scored_pairs`` and ``selected_pairs``: the model's work, not the
form's (a chunk's kernel computes every visible pair).  A prompt token
leaves out what a prefill program prunes of its last layer.  Source:
the program's own spans; a program whose spans carry no such counts
gives nothing."""
from benchmark import flops_keye as fk
from benchmark import program_spans as ps


def read(run):
    got = ps.serving(run)
    if got is None or not run.get("quiet_s"):
        return None
    s = fk.span_sums(got["quiet"])
    if "indexer_positions" not in s or (
            s.get("chunks") and "selected_pairs" not in s):
        return None
    prefilled, decoded = s.get("tokens", 0), s.get("decode_rows", 0)
    if not prefilled + decoded:
        return None
    flops = fk.serve_flops(
        run["config"], prefilled, decoded,
        s.get("moe_assignments", 0) + s.get("prefill_moe_assignments", 0),
        s["indexer_positions"], s.get("selected_positions", 0),
        s.get("scored_pairs", 0), s.get("selected_pairs", 0))
    return 100.0 * flops / run["quiet_s"] / (
        run["chips"] * run["peaks"]["bf16_flops"])
