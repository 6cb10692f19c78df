"""experts_touched_share.* (%): of the routed experts of all expert
layers, the share that received a live row's token in a decode step:
sum of ``experts_touched`` (a count ``LLMEngine.step()`` writes on its
``serving.step`` root) over experts x expert layers x decode steps, the
quiet steps.  It is the share of the experts' weights a decode step has
to read.  Source: the program's own spans; a program whose roots carry
no such count gives nothing."""
from benchmark import flops_moe_mla
from benchmark import program_spans as ps


def read(run):
    got = ps.serving(run)
    if got is None:
        return None
    touched = [root[ps.COUNTS]["experts_touched"] for root, _ in got["quiet"]
               if "experts_touched" in root[ps.COUNTS]]
    if not touched:
        return None
    cfg = run["config"]
    experts = int(cfg["n_routed_experts"]) * flops_moe_mla.moe_layers(cfg)
    return 100.0 * sum(touched) / (experts * len(touched))
