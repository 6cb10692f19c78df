"""indexer_blocks_per_copy.* (blocks): of the pool blocks the indexer's
decode walk copied in the quiet steps, how many a DMA moved: sum of
``kv_blocks_walked`` over sum of ``ik_copies``, the two counts
``LLMEngine.step()`` writes on its ``serving.step`` root.  The walk
copies each run of adjacent block ids inside a chunk in power-of-two
pieces, so this reads 1 where no two neighbours of a table lie side by
side in the pool and up to the chunk's blocks where a whole table is one
run.  The counts are the host's, from the tables and positions the step
sends, by the kernel's own rule (`ops.pallas.pool_walk_copies`).
Source: the program's own spans; a program whose roots carry no
``ik_copies`` gives nothing."""
from benchmark import program_spans


def read(run):
    got = program_spans.serving(run)
    if got is None:
        return None
    counts = [root[program_spans.COUNTS] for root, _ in got["quiet"]]
    copies = sum(c.get("ik_copies", 0) for c in counts)
    if not copies:
        return None
    return sum(c.get("kv_blocks_walked", 0) for c in counts) / copies
