"""held_assignment_share.* (%): of the assignments the routers made in
the quiet steps (``moe_assignments_routed`` + its ``prefill_`` twin:
live tokens x experts per token x the expert layers that ran), the
share that fell on the experts HELD here (``moe_assignments`` +
``prefill_moe_assignments``, the programs' own load).  Even routing over
320 experts of which 40 are held gives 12.5.  Source: the program's own
spans; a program whose roots carry no routed count gives nothing."""
from benchmark import program_spans as ps


def read(run):
    got = ps.serving(run)
    if got is None:
        return None
    counts = [root[ps.COUNTS] for root, _ in got["quiet"]]
    routed = sum(c.get("moe_assignments_routed", 0)
                 + c.get("prefill_moe_assignments_routed", 0)
                 for c in counts)
    if not routed:
        return None
    return 100.0 * sum(c.get("moe_assignments", 0)
                       + c.get("prefill_moe_assignments", 0)
                       for c in counts) / routed
