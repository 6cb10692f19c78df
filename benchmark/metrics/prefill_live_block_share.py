"""prefill_live_block_share.* (%): of the pool blocks the prefill
programs' attention read in the quiet steps, the share that held a
position one of the chunk's tokens sees: sum of ``kv_blocks_live`` over
sum of ``kv_blocks_walked``, the two counts ``LLMEngine._prefill``
writes on each ``serving.prefill`` span (a child of its step's root).
What is missing to 100 is read for nobody: blocks under a bucket's
padding rows where a kernel walks, every table column past the chunk
where the XLA gather reads the whole table.  Source: the program's own
spans; a program whose chunk spans carry no such counts gives
nothing."""
from benchmark import program_spans

PREFILL = "serving.prefill"


def read(run):
    got = program_spans.serving(run)
    if got is None:
        return None
    counts = [c[program_spans.COUNTS] for _, kids in got["quiet"]
              for c in kids if c[program_spans.NAME] == PREFILL]
    walked = sum(c.get("kv_blocks_walked", 0) for c in counts)
    if not walked:
        return None
    return 100.0 * sum(c.get("kv_blocks_live", 0) for c in counts) / walked
