"""state_cache_share.* (%): of the cache bytes the decoding rows held in
the quiet steps, the share that is recurrent state: half of
``state_bytes_rw`` (a step reads and writes each live row's state once)
over that plus the rows' live K/V blocks (``kv_blocks_live`` x block
size x the GQA layers' K and V bytes a token).  It says how far the
small cache, not the context, sets a row's memory.  Source: the
program's own spans; a program without state counts gives nothing."""
from benchmark import flops_hybrid as fh
from benchmark import program_spans as ps


def read(run):
    got = ps.serving(run)
    if got is None:
        return None
    counts = [root[ps.COUNTS] for root, _ in got["quiet"]]
    state = sum(c.get("state_bytes_rw", 0) for c in counts) / 2.0
    if not state:
        return None
    per_block = int(run["mix"]["engine"]["block_size"]) \
        * fh.kv_bytes_per_token(run["config"])
    return 100.0 * state / (state + per_block * sum(
        c.get("kv_blocks_live", 0) for c in counts))
