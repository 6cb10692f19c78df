"""window_blocks_held_share.* (%): of the blocks the decoding rows would
hold in the window layers' planes under one full table
(``window_blocks_live`` + ``window_blocks_saved``, the two counts
``LLMEngine.step()`` writes on its ``serving.step`` root), the share
they do hold (``window_blocks_live``), over the quiet steps.  Lower is
the mechanism working: a reading of 100 means the window kind never
handed a block back.  Source: the program's own spans; a program whose
roots carry no such counts gives nothing."""
from benchmark import program_spans


def read(run):
    got = program_spans.serving(run)
    if got is None:
        return None
    counts = [root[program_spans.COUNTS] for root, _ in got["quiet"]]
    held = sum(c.get("window_blocks_live", 0) for c in counts)
    whole = held + sum(c.get("window_blocks_saved", 0) for c in counts)
    if not whole:
        return None
    return 100.0 * held / whole
