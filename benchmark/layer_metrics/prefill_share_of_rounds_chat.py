from benchmark import readers


def read(run):
    """The decoder's own prefill_s over prefill_s + decode_s."""
    prefill = readers.delta(run, "prefill_s")
    if prefill is None:
        return None
    return 100.0 * readers.ratio(
        prefill, prefill + readers.delta(run, "decode_s"))
