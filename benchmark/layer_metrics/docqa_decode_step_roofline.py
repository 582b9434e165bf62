from benchmark import readers, readers_latent_moe


def read(run):
    """The whole decode step against the chip: what is always streamed
    (attention, dense MLP, shared experts, routers, head), the experts
    that were hit, the live latent rows, over the memory bandwidth (or
    their operations over the peak, whichever bounds), over the time a
    step took."""
    return readers_latent_moe.roofline_share(
        run, readers_latent_moe.step_work(run), readers.decode_step_ms(run))
