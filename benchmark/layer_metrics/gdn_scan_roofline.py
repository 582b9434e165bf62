from benchmark import readers_gated_delta


def read(run):
    """The gated delta rule over the prompts prefilled in the traced span
    against the chip: the rule's OWN operations a token (7 Dk Dv a head,
    whatever form computes them) over the peak, or the state in and out
    once a piece and q, k, v, o a token over the memory bandwidth,
    whichever bounds, over the device time under `aiko.gdn_scan` in
    `jit_admit` and `jit_extend`.  The chunked form does more operations
    than the rule and pads a prompt to its bucket: the share is low."""
    seconds = readers_gated_delta.scan_seconds(run)
    return readers_gated_delta.roofline_share(
        run, readers_gated_delta.scan_work(run),
        None if seconds is None else 1e3 * seconds)
