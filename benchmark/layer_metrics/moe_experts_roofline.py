from benchmark import readers_latent_moe
from benchmark.trace import regions


def read(run):
    """The routed experts against the chip: the weights of the experts
    that were HIT (the decoder's `moe_experts_hit`, a step) over the
    memory bandwidth, or their operations on the pairs that landed here
    over the MXU's peak, whichever bounds, over the device time under
    `aiko.moe_experts` in a decode step."""
    return readers_latent_moe.roofline_share(
        run, readers_latent_moe.experts_work(run),
        regions.step_region_ms(run, "aiko.moe_experts"))
