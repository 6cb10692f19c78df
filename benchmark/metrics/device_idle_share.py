"""device_idle_share.* (%): 1 - union of device 0's operation intervals
over the traced window.  One reader for every suffix."""
from benchmark import trace


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"]:
        return None
    t0, t1 = trace.window_of(tr)
    ops = tr["devices"][min(tr["devices"])]["ops"]
    return 100.0 * (1.0 - trace.busy_ns(ops) / (t1 - t0))
