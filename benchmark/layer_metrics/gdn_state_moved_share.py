from benchmark import readers_gated_delta


def read(run):
    """Slot states the decode steps' recurrence changed (the slots that
    decoded: what the kernel moves, once in and once out) over those the
    recurrent layers hold (every slot), in the traced span."""
    return readers_gated_delta.traced_share(run, "gdn_states_moved",
                                            "gdn_states_held")
