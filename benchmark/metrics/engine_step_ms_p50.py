"""engine_step_ms_p50.* (ms): median of the harness's clock around
``LLMEngine.step()`` over the untraced part of the window (a span around
the call into the layer; a median of pieces, so not an end-to-end
metric)."""
from benchmark import stats


def read(run):
    return stats.percentile(run.get("step_ms") or [], 50)
