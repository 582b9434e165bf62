from benchmark import readers_hybrid_sparse


def read(run):
    """The sparse layer's choice and attention in a decode step against
    the chip: the pooled indexer keys of every live position once and the
    CHOSEN latent rows once (the decoder's `dsa_positions_attended`, a
    step) over the memory bandwidth, or their operations over the peak,
    whichever bounds, over the device time under `aiko.dsa_index`,
    `aiko.attn_core` and `aiko.dsa_relayout` in a step: the bytes are
    what the step must move, the time is what it takes, the copy of the
    whole leaf included."""
    return readers_hybrid_sparse.roofline_share(
        run, readers_hybrid_sparse.sparse_core_work(run),
        readers_hybrid_sparse.sparse_core_ms(run))
