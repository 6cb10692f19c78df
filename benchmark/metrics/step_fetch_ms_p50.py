"""step_fetch_ms_p50.* (ms): median over the quiet steps of
``serving.decode.fetch``: what is left of the copy of the step's logits
to the host once the wait for the device (``serving.decode.wait``, which
queues the copy behind the program) has returned.  Source: the program's
own spans."""
from benchmark import program_spans


def read(run):
    return program_spans.phase_ms_p50(run, ("serving.decode.fetch",))
