"""full_paged_roofline.* (%): the least time the chip could take for the
traced decode steps' paged attention IN THE FULL-ATTENTION LAYERS of a
model that also has window layers, over the summed device time of the
paged decode kernel's events, by its name (`paged_decode_attention`; the
window layers' kernel carries another).  Work, from the traced steps' own
spans (`flops_laguna.paged_work`): K and V of the blocks the decoded rows
live in (``kv_blocks_live``) x the full layers, q and o, and the pairs'
FLOPs by those layers' head count; the larger of FLOPs over the bf16
peak and bytes over the HBM peak, which binds is printed.  The count is
of the work, whatever implements it.  Nothing matched, or a program
whose roots carry no ``window_blocks_band`` (another model's full
layers have readers of their own), gives nothing, never 0."""
from benchmark import flops, flops_laguna as fl, harness, trace
from benchmark import program_spans as ps

PATTERN = r"(?<!latent_)paged_decode_attention"
KIND, BLOCKS = fl.FULL, "kv_blocks_live"


def read(run, pattern=PATTERN, kind=KIND, blocks_key=BLOCKS):
    tr, got = run.get("trace"), ps.serving(run)
    if not tr or not tr["devices"] or got is None:
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_s = trace.named_sum_ns(ops, pattern) / 1e9
    first = got["first_traced"]
    blocks = rows = 0.0
    banded = False
    for root, _ in got["steps"][first:first + got["n_traced"]]:
        counts = root[ps.COUNTS]
        banded |= "window_blocks_band" in counts
        blocks += counts.get(blocks_key, 0)
        rows += counts.get("decode_rows", 0)
    if kernel_s <= 0 or not banded or not blocks:
        return None
    least, binds = flops.roofline_seconds(
        *fl.paged_work(run["config"], kind, blocks, rows,
                       int(run["mix"]["engine"]["block_size"])),
        run["peaks"])
    harness.say(f"{run['metric']}: {binds} binds, least {least * 1e3:.2f} "
                f"ms of {kernel_s * 1e3:.2f} ms in the kernel")
    return 100.0 * least / kernel_s
