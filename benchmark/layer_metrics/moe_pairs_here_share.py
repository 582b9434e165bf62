from benchmark import readers, readers_latent_moe


def read(run):
    """Of the token-expert pairs the router chose over the window's decode
    steps, the share that landed on experts held here, % (one chip's share
    of the deployment: held / all experts where routing is even)."""
    if readers.delta(run, "moe_pairs_routed") is None:
        return None
    return readers_latent_moe.share(run, "moe_pairs_here", "moe_pairs_routed")
