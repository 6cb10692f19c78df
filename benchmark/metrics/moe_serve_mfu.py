"""moe_serve_mfu.* (%): model FLOPs of the quiet steps of a routed,
latent-attention model over their time and the chip's bf16 peak: the
whole serving step's share of the peak.  2 FLOPs per ACTIVE matmul
weight per token pushed through the layers (attention, shared experts,
the experts a token is sent to, the dense layer; `flops_moe_mla`), the
head for decoded tokens, and attention by context: a prefill chunk's
pairs from its span's ``tokens`` and ``ctx``, a decode step's from the
blocks its rows live in (``kv_blocks_live``, half a block a row taken
off).  A prompt token counts every layer but the last, whose output no
chunk needs.  Source: the program's own spans; a program whose prefill
spans carry no such counts gives nothing."""
from benchmark import flops_moe_mla as fm
from benchmark import program_spans as ps


def read(run):
    got = ps.serving(run)
    if got is None or not run.get("quiet_s"):
        return None
    block = int(run["mix"]["engine"]["block_size"])
    prefilled = decoded = pairs = chunk_pairs = 0.0
    for root, kids in got["quiet"]:
        counts = root[ps.COUNTS]
        rows = counts.get("decode_rows", 0)
        decoded += rows
        pairs += max(0.0, (counts.get("kv_blocks_live", 0) - rows / 2.0)
                     * block)
        for kid in kids:
            if kid[ps.NAME] != "serving.prefill":
                continue
            if "tokens" not in kid[ps.COUNTS]:
                return None
            n, ctx = kid[ps.COUNTS]["tokens"], kid[ps.COUNTS]["ctx"]
            prefilled += n
            chunk_pairs += fm.visible_pairs(n, ctx)
    if not prefilled + decoded:
        return None
    rate = fm.serve_flops(run["config"], prefilled, decoded, chunk_pairs,
                          pairs) / run["quiet_s"]
    return 100.0 * rate / (run["chips"] * run["peaks"]["bf16_flops"])
