from benchmark import readers_latent_moe
from benchmark.trace import regions


def read(run):
    """The latent walk against the chip: every live cached row read once
    (576 values: it is K and V both) over the memory bandwidth, or its
    2 x heads x (576 + 512) operations a row over the MXU's peak,
    whichever bounds, over the device time under `aiko.attn_core` in a
    decode step.  The kernel reads a row twice (a K pass, a V pass) and
    pads it to 640 lanes, so a perfect walk reads 45%."""
    return readers_latent_moe.roofline_share(
        run, readers_latent_moe.attention_work(run),
        regions.step_region_ms(run, "aiko.attn_core"))
