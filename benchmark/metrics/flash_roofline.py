"""flash_roofline (%): the least time the chip could take for the traced
steps' causal attention (forward + backward, all layers, from the cell's
shapes) over the summed device time of the flash kernels' events.

The kernels carry no stable name yet (PERF.md, Open questions): their
events are matched by PATTERN, read off a trace by hand.  A trace in
which nothing matches gives nothing, never 0."""
from benchmark import flops, trace

# The flash kernels are the train step's only Mosaic kernels.  The v5e
# trace names them after the autodiff rule that called them (jvp__.N the
# forward, transpose_jvp___.N the two backward kernels; my chip trace,
# PR 24), so they are matched as "a Mosaic kernel that is not the paged
# decode kernel".
PATTERN = r"^mosaic:(?!paged)"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"] or not run.get("traced_steps"):
        return None
    ops = tr["devices"][min(tr["devices"])]["ops"]
    kernel_s = trace.named_sum_ns(ops, PATTERN) / 1e9
    if kernel_s <= 0:
        return None
    mix = run["mix"]
    # one chip's share of the step: batch over dp, heads over mp
    work_f, work_b = flops.flash_step_work(
        run["config"], int(mix["batch"]), int(mix["seq"]))
    least, _ = flops.roofline_seconds(work_f / run["chips"],
                                      work_b / run["chips"], run["peaks"])
    return 100.0 * least * run["traced_steps"] / kernel_s
