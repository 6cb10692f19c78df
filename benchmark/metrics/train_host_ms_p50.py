"""train_host_ms_p50 (ms): median duration of the program's
``train.call`` span (the whole of ``TrainStep.__call__`` on the host)
over the calls before the traced steps.  The host dispatches ahead of
the device, so this is how short a device step may get before the host
binds.  Prints one line with where it goes, from the span's children:
``train.call.lookup`` (state and executable resolved),
``train.call.dispatch`` (the runner call) and the rest (the new
parameters handed to the model).  Source: the program's own spans; needs
no clock alignment."""
import statistics

from benchmark import harness, program_spans
from benchmark.program_spans import NAME, PARENT, SID, T0, T1


def read(run):
    recs = program_spans.records(run) or ()
    calls = [r for r in recs if r[NAME] == "train.call"]
    calls = calls[:len(calls) - int(run.get("traced_steps") or 0)]
    if not calls:
        return None
    mine = {r[SID] for r in calls}
    p50 = {name: statistics.median(
        [(r[T1] - r[T0]) / 1e6 for r in recs
         if r[NAME] == name and r[PARENT] in mine] or [float("nan")])
        for name in ("train.call.lookup", "train.call.dispatch")}
    whole = statistics.median((r[T1] - r[T0]) / 1e6 for r in calls)
    harness.say("train.call ms p50 " + f"{whole:.4f}: " + " ".join(
        f"{name}={ms:.4f}" for name, ms in p50.items()))
    return whole
