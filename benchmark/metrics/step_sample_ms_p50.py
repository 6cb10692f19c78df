"""step_sample_ms_p50.* (ms): median over the quiet steps of
``serving.sample``: per row the finite check, the sampling, the
callbacks and the finish.  Source: the program's own spans."""
from benchmark import program_spans


def read(run):
    return program_spans.phase_ms_p50(run, ("serving.sample",))
