from benchmark import readers, readers_latent_moe


def read(run):
    """Of the experts held here, the share that at least one token reached,
    over the window's decode steps and sparse layers, %: what of the held
    experts' weights a step has to stream.  From the decoder's counters
    (`moe_experts_hit` over `moe_layer_steps` x the experts held)."""
    held = run["counters"]["after"].get("moe_experts_held")
    if not held or readers.delta(run, "moe_layer_steps") is None:
        return None
    return readers_latent_moe.share(run, "moe_experts_hit",
                                    "moe_layer_steps", held)
