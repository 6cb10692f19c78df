"""paged_live_block_share.* (%): of the pool blocks the paged decode
kernel walked in the quiet steps, the share that held a decoding row's
context: sum of ``kv_blocks_live`` over sum of ``kv_blocks_walked``, the
two counts ``LLMEngine.step()`` writes on its ``serving.step`` root.
What is missing to 100 is walked for nobody: dead slots and table columns
past a row's context.  Source: the program's own spans; a program whose
roots carry no such counts gives nothing."""
from benchmark import program_spans


def read(run):
    got = program_spans.serving(run)
    if got is None:
        return None
    counts = [root[program_spans.COUNTS] for root, _ in got["quiet"]]
    walked = sum(c.get("kv_blocks_walked", 0) for c in counts)
    if not walked:
        return None
    return 100.0 * sum(c["kv_blocks_live"] for c in counts) / walked
