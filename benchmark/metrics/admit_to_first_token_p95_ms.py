"""admit_to_first_token_p95_ms.* (ms): 95th percentile of first token
minus admission over the requests that arrived after the first quiet
step began and had their first token before the profiler started, from
the marks of the program's ``serving.request`` spans (a span starts at
the request's arrival): the part of the TTFT tail that prefill and the
first decode own, beside the queue wait.  Prints one line that splits it
at the ``prefill_done`` mark: what chunked prefill took, and what the
first decode step after it took.  Source: the program's own spans."""
from benchmark import harness, program_spans, stats
from benchmark.program_spans import COUNTS, NAME, T0


def read(run):
    got = program_spans.serving(run)
    if got is None or not got["quiet"]:
        return None
    opened = got["quiet"][0][0][T0]
    cut = got["steps"][got["first_traced"]][0][T0]
    marks = [r[COUNTS] for r in got["records"]
             if r[NAME] == "serving.request" and r[T0] >= opened]
    marks = [m for m in marks if "admitted" in m and "prefill_done" in m
             and m.get("first_token", cut) < cut]

    def p95(a, b):
        return stats.percentile([(m[b] - m[a]) / 1e6 for m in marks], 95)

    if marks:
        harness.say(
            f"admitted -> prefill_done ms p95 "
            f"{p95('admitted', 'prefill_done'):.4f}, prefill_done -> "
            f"first_token ms p95 {p95('prefill_done', 'first_token'):.4f} "
            f"over {len(marks)} requests")
    return p95("admitted", "first_token")
