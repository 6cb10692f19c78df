"""hybrid_serve_mfu.* (%): model FLOPs of the quiet steps of a hybrid
decoder (GQA + gated delta-rule layers, one chip's share of the routed
experts) over their time and the chip's bf16 peak: the whole serving
step's share of the peak.  `flops_hybrid.serve_flops`: 2 FLOPs per matmul
weight a token REALLY multiplies here -- of the routed experts the
assignments that fell on the HELD ones, as the programs count them
(``moe_assignments`` + ``prefill_moe_assignments``), not
`num_experts_per_tok` a token --, the KDA layers' state FLOPs a token,
the head for decoded tokens, and attention by context in the GQA layers
alone: a chunk's pairs from its span's ``tokens`` and ``ctx``, a decode
step's from the blocks its rows live in (``kv_blocks_live``, half a
block a row taken off).  A prompt token leaves out what a prefill
program prunes of its last layer.  Source: the program's own spans; a
program whose roots carry no ``state_slots_live`` gives nothing."""
from benchmark import flops_hybrid as fh
from benchmark import program_spans as ps


def read(run):
    got = ps.serving(run)
    if got is None or not run.get("quiet_s"):
        return None
    block = int(run["mix"]["engine"]["block_size"])
    prefilled = decoded = pairs = chunk_pairs = local = 0.0
    stateful = False
    for root, kids in got["quiet"]:
        counts = root[ps.COUNTS]
        stateful |= "state_slots_live" in counts
        rows = counts.get("decode_rows", 0)
        decoded += rows
        pairs += max(0.0, (counts.get("kv_blocks_live", 0) - rows / 2.0)
                     * block)
        local += counts.get("moe_assignments", 0) \
            + counts.get("prefill_moe_assignments", 0)
        for kid in kids:
            if kid[ps.NAME] == "serving.prefill":
                n, ctx = kid[ps.COUNTS]["tokens"], kid[ps.COUNTS]["ctx"]
                prefilled += n
                chunk_pairs += fh.visible_pairs(n, ctx)
    if not stateful or not prefilled + decoded:
        return None
    rate = fh.serve_flops(run["config"], prefilled, decoded, chunk_pairs,
                          pairs, local) / run["quiet_s"]
    return 100.0 * rate / (run["chips"] * run["peaks"]["bf16_flops"])
