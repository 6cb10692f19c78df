"""step_dispatch_ms_p50.* (ms): median over the quiet steps of the host
time that hands work to the device: every ``serving.prefill`` of the
step (numpy prep + dispatch per request and chunk), the numpy rebuild of
the decode program's four arrays (``serving.decode.prepare``) and the
decode dispatch until it returns.  Source: the program's own spans."""
from benchmark import program_spans


def read(run):
    return program_spans.phase_ms_p50(
        run, ("serving.prefill", "serving.decode.prepare",
              "serving.decode.dispatch"))
