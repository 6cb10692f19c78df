"""step_schedule_ms_p50.* (ms): median over the quiet steps of the time
``LLMEngine.step()`` spends in its ``serving.schedule`` spans (expiry
sweep and admission before the prefill lane, block growth and preemption
after it).  Source: the program's own spans."""
from benchmark import program_spans


def read(run):
    return program_spans.phase_ms_p50(run, ("serving.schedule",))
