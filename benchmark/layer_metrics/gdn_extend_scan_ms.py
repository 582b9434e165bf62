from benchmark import readers_gated_delta


def read(run):
    """Device ms a prompt's piece spends under `aiko.gdn_scan` (the chunked
    form of the gated delta rule in every recurrent layer) inside
    `jit_admit` and `jit_extend`: a request admitted whole is one piece, a
    chunk of a longer prompt one."""
    seconds = readers_gated_delta.scan_seconds(run)
    count = readers_gated_delta.pieces(run)
    if seconds is None or not count:
        return None
    return 1e3 * seconds / count
