from benchmark.trace import regions


def read(run):
    """Device ms a decode step spends under `aiko.dsa_select`: the exact
    top `topk` of every slot's index scores, all layers, and nothing
    else (the scores are `dsa_step_index_ms`'s, the fetch of the chosen
    rows `dsa_step_attn_core_ms`'s)."""
    return regions.step_region_ms(run, "aiko.dsa_select")
