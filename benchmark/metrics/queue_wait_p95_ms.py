"""queue_wait_p95_ms.* (ms): 95th percentile of admission time minus due
time over the requests admitted before the profiler started.  Source: the
harness's clock (the program's own histogram keeps a 1,024-sample
reservoir and is no source for a tail)."""
from benchmark import stats


def read(run):
    return stats.percentile(run.get("queue_wait_ms") or [], 95)
