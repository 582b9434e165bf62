from benchmark import readers


def read(run):
    """K/V positions that the step's gather READ from the pool over the
    positions it attended (`dsa_rows_fetched` / `dsa_positions_attended`
    over the window): 1.0 where single rows are fetched and every
    attended row came from the pool, less by the round's own rows (which
    are attended from the side buffers and fetched from nowhere), more
    where a gather takes whole tiles for single rows."""
    return readers.ratio(readers.delta(run, "dsa_rows_fetched"),
                         readers.delta(run, "dsa_positions_attended"))
