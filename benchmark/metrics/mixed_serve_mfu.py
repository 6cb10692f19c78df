"""mixed_serve_mfu.* (%): model FLOPs of the quiet steps of a decoder of
full and window attention layers (one chip's share of the routed experts)
over their time and the chip's bf16 peak: the whole serving step's share
of the peak.  `flops_laguna.serve_flops`: 2 FLOPs per matmul weight a
token REALLY multiplies here -- attention by each layer's own head count,
the dense layer, router and shared expert, of the routed experts the
assignments that fell on the HELD ones, as the programs count them
(``moe_assignments`` + ``prefill_moe_assignments``), the head for decoded
tokens -- and attention pairs by kind: a full layer's query sees its
context, a window layer's at most the window.  A chunk's pairs come from
its span's ``tokens`` and ``ctx``; a decode step's from the blocks its
rows live in (``kv_blocks_live``, half a block a row taken off) and, for
the window kind, from its band's blocks (``window_blocks_band``), at
most the window a row.  A prompt token leaves out what a prefill program
prunes of its last layer.  Source: the program's own spans; a program
whose roots carry no ``window_blocks_band`` gives nothing."""
from benchmark import flops_laguna as fl
from benchmark import program_spans as ps


def read(run):
    got = ps.serving(run)
    if got is None or not run.get("quiet_s"):
        return None
    cfg = run["config"]
    block = int(run["mix"]["engine"]["block_size"])
    window = int(cfg["sliding_window"])
    prefilled = decoded = local = 0.0
    pairs = {fl.FULL: 0.0, fl.WINDOW: 0.0}
    chunk_pairs = {fl.FULL: 0.0, fl.WINDOW: 0.0}
    banded = False
    for root, kids in got["quiet"]:
        counts = root[ps.COUNTS]
        banded |= "window_blocks_band" in counts
        rows = counts.get("decode_rows", 0)
        decoded += rows
        pairs[fl.FULL] += max(0.0, (counts.get("kv_blocks_live", 0)
                                    - rows / 2.0) * block)
        pairs[fl.WINDOW] += min(float(rows * window), max(0.0, (
            counts.get("window_blocks_band", 0) - rows / 2.0) * block))
        local += counts.get("moe_assignments", 0) \
            + counts.get("prefill_moe_assignments", 0)
        for kid in kids:
            if kid[ps.NAME] == "serving.prefill":
                n, ctx = kid[ps.COUNTS]["tokens"], kid[ps.COUNTS]["ctx"]
                prefilled += n
                chunk_pairs[fl.FULL] += fl.visible_pairs(n, ctx)
                chunk_pairs[fl.WINDOW] += fl.visible_pairs(n, ctx, window)
    if not banded or not prefilled + decoded:
        return None
    rate = fl.serve_flops(cfg, prefilled, decoded, chunk_pairs, pairs,
                          local) / run["quiet_s"]
    return 100.0 * rate / (run["chips"] * run["peaks"]["bf16_flops"])
