def read(run):
    """Output tokens delivered inside the window, over its length."""
    return run["tokens_in_window"] / run["seconds"]
