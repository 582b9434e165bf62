from benchmark import readers
from benchmark.trace import regions


def read(run):
    """Device ms a prefill chunk spends under `aiko.mla_expand` (W_kvb over
    the latent rows of the chunk and of its prefix, piece by piece): the
    region's time inside `jit_extend` over the traced span, over the chunks
    the decoder dispatched in it.  None where no operation carries the
    scope (another program)."""
    trace, _ = regions.of_run(run)
    chunks = readers.delta(run, "prefill_chunks", "trace_counters")
    if trace is None or not chunks:
        return None
    found = regions.region_seconds(trace, ["jit_extend"])
    if not found or "aiko.mla_expand" not in found["seconds"]:
        return None
    return 1e3 * found["seconds"]["aiko.mla_expand"] / chunks
