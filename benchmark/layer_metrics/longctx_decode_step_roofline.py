from benchmark import readers, readers_sparse_gqa


def read(run):
    """The whole decode step against the chip: what is always streamed
    (the head's slice with it), the experts that were HIT, the indexer
    keys of everything live and the chosen K/V rows, over the memory
    bandwidth (or their operations over the peak, whichever bounds),
    over the time a step took."""
    return readers_sparse_gqa.roofline_share(
        run, readers_sparse_gqa.step_work(run),
        readers.decode_step_ms(run))
