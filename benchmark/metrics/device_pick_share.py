"""device_pick_share.* (%): of the rows the quiet steps decoded, the share
whose token the decode program chose itself and whose logits never left
the device: sum of ``rows_picked_on_device`` over sum of ``decode_rows``,
two counts ``LLMEngine.step()`` writes on its ``serving.step`` root.  What
is missing to 100 are the rows of steps that copied their logits to the
host: a step with a row that draws its token there (``do_sample``), or
one whose rows somebody read.  Source: the program's own spans; a program
whose roots carry no such count gives nothing."""
from benchmark import program_spans


def read(run):
    got = program_spans.serving(run)
    if got is None:
        return None
    counts = [root[program_spans.COUNTS] for root, _ in got["quiet"]]
    counts = [c for c in counts if "rows_picked_on_device" in c]
    rows = sum(c["decode_rows"] for c in counts)
    if not rows:
        return None
    return 100.0 * sum(c["rows_picked_on_device"] for c in counts) / rows
