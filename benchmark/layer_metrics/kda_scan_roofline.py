from benchmark import ops_bytes_hybrid_sparse as ops
from benchmark import readers_hybrid_sparse


def read(run):
    """The gated delta rule over a prefill chunk against the chip: the
    recurrence's OWN operations a token (7 D^2 a head, whatever form
    computes them) over the peak, or the state in and out once a chunk
    and q, k, v, g, o a token over the memory bandwidth, whichever bounds,
    over the device time under `aiko.kda_core` in `jit_extend`, a chunk.
    The region holds the convolution and the gates too, and the chunked
    form does more operations than the recurrence: the share is low."""
    chunk = run["config"]["serving"].get("prefill_chunk")
    if not chunk:
        return None
    return readers_hybrid_sparse.roofline_share(
        run, ops.kda_recurrence(run["config"], chunk, 1),
        readers_hybrid_sparse.extend_region_ms(run, "aiko.kda_core"))
