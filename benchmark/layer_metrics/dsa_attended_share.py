from benchmark import readers


def read(run):
    """Of the positions live in the sparse-attention layers over the
    window's decode steps, the share that was attended, %: the chosen
    groups, the open group and the round's own rows.  From the decoder's
    counters (`dsa_positions_attended` over `dsa_positions_live`)."""
    value = readers.ratio(readers.delta(run, "dsa_positions_attended"),
                          readers.delta(run, "dsa_positions_live"))
    return None if value is None else 100.0 * value
