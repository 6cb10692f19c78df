"""train_step_ms_p50 (ms): median device duration of the traced steps'
program executions (the ``XLA Modules`` line of device 0; the step is the
module with the most summed time).  The host dispatches steps ahead of
the device, so a host time per step would measure the enqueue."""
import statistics


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"]:
        return None
    mods = tr["devices"][min(tr["devices"])]["modules"]
    by_name = {}
    for name, _, dur in mods:
        by_name.setdefault(name, []).append(dur)
    if not by_name:
        return None
    durs = max(by_name.values(), key=sum)
    return statistics.median(durs) / 1e6
