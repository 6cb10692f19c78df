"""first_token_p95_ms.* (ms): 95th percentile, over the requests due in
the window whose first token came before the profiler started, of
first-token time minus the time the request was DUE: the cell's TTFT
tail.  It stands per layer because its runs spread too widely for an
end-to-end bound (PERF.md, PR 24's refusal round).  Source: the harness's
clock."""
from benchmark import stats


def read(run):
    return stats.percentile(run.get("ttft_ms") or [], 95)
