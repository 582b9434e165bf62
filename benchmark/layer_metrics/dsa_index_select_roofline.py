from benchmark import readers_sparse_gqa


def read(run):
    """Scoring and choosing in a decode step against the chip: the
    indexer key of every live position once (its 64 lanes, whatever the
    leaf pads them to) over the memory bandwidth, or its 16 heads' dots
    over the peak, whichever bounds, over the device time under
    `aiko.dsa_index` and `aiko.dsa_select` in a step."""
    return readers_sparse_gqa.roofline_share(
        run, readers_sparse_gqa.index_select_work(run),
        readers_sparse_gqa.index_select_ms(run))
