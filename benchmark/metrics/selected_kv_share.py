"""selected_kv_share.* (%): of the positions the decode steps' indexer
scored, the share whose K and V the attention read: sum of
``selected_positions`` over sum of ``indexer_positions``, the two counts
``LLMEngine.step()`` writes on its ``serving.step`` root for an op that
picks what it reads, over the quiet steps.  On the TPU path a row reads
``min(ctx + 1, topk)`` positions; the XLA form reads its whole table,
and dense attention would read 100.  The counts are the host's, from
the lengths sent and the op's gate (`ops.pallas.sparse_positions_read`):
this share RESTATES the path's rule, and reads the same whatever the
device copied.  That the path reads the picks alone is held by
`tests/test_keye_vl.py::test_the_kernel_forms_are_the_xla_form` (the
TPU forms' output against the XLA form's, which attends the top-k by a
mask) and by the gather in `sparse_attention.picked_attention`.
Source: the program's own spans; a program whose roots carry no such
counts gives nothing."""
from benchmark import flops_keye as fk
from benchmark import program_spans as ps


def read(run):
    got = ps.serving(run)
    if got is None:
        return None
    sums = fk.span_sums(got["quiet"])
    if not sums.get("indexer_positions"):
        return None
    return 100.0 * sums["selected_positions"] / sums["indexer_positions"]
