"""window_paged_roofline.* (%): `full_paged_roofline`'s rule for the
WINDOW layers: the kernel named `paged_window_decode_attention` against
K and V of the blocks that hold a position the rows' queries see
(``window_blocks_band``: the band's blocks, the least a sound walk
reads, whatever the kernel walks) x the window layers, q and o, and the
pairs' FLOPs by the window layers' head count."""
from benchmark import flops_laguna as fl, harness

PATTERN = r"paged_window_decode_attention"


def read(run):
    return harness.load_reader("full_paged_roofline")(
        run, PATTERN, fl.WINDOW, "window_blocks_band")
