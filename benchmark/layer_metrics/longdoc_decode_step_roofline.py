from benchmark import readers, readers_hybrid_sparse


def read(run):
    """The whole decode step against the chip: what is always streamed,
    the experts that were hit, the KDA state of the slots that decode in
    and out, the pooled keys and the chosen rows, over the memory
    bandwidth (or their operations over the peak, whichever bounds), over
    the time a step took."""
    return readers_hybrid_sparse.roofline_share(
        run, readers_hybrid_sparse.step_work(run),
        readers.decode_step_ms(run))
