from benchmark import readers_sparse_gqa
from benchmark.trace import regions


def read(run):
    """The attention over the chosen positions in a decode step against
    the chip: each attended position's K and V rows once (2,048 B) over
    the memory bandwidth, or the heads' scores and outputs over the
    peak, whichever bounds, over the device time under `aiko.attn_core`
    in a step (the gather of the rows and the softmax over them)."""
    return readers_sparse_gqa.roofline_share(
        run, readers_sparse_gqa.sparse_attention_work(run),
        regions.step_region_ms(run, "aiko.attn_core"))
