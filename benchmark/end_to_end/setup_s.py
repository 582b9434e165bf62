def read(run):
    """Process start to the opening of the window: loading, weights,
    warm-up and, in a run that compiles, compilation."""
    return run["set_up_seconds"]
