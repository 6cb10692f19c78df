"""first_token_mean_ms.* (ms): mean, over the same requests as
``first_token_p95_ms``, of first-token time minus due time: the steadier
statistic beside the tail.  Source: the harness's clock."""


def read(run):
    ttft = run.get("ttft_ms") or []
    return sum(ttft) / len(ttft) if ttft else None
