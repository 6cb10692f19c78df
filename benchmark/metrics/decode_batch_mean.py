"""decode_batch_mean.* (rows): the program's own ``serving_decode_batch``
histogram, sum over count, between the window's opening and the moment
the profiler started.  Source: program counter."""


def read(run):
    if not run.get("decode_batch_count"):
        return None
    return run["decode_batch_sum"] / run["decode_batch_count"]
