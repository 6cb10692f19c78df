"""chained_row_share.* (%): of the rows the quiet steps dispatched, the
share whose token the decode program took from the program before it, on
the device, before the host had seen it: sum of ``rows_chained`` over sum
of ``decode_rows``, two counts ``LLMEngine.step()`` writes on its
``serving.step`` root.  What is missing to 100 are the rows the host
fed: a request's first decode row, and every row of a step that waited
for its own program (a ``do_sample`` row draws its token on the host).
Source: the program's own spans; a program whose roots carry no such
count gives nothing."""
from benchmark import program_spans


def read(run):
    got = program_spans.serving(run)
    if got is None:
        return None
    counts = [root[program_spans.COUNTS] for root, _ in got["quiet"]]
    counts = [c for c in counts if "rows_chained" in c]
    rows = sum(c["decode_rows"] for c in counts)
    if not rows:
        return None
    return 100.0 * sum(c["rows_chained"] for c in counts) / rows
