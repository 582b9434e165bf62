from benchmark.trace import regions


def read(run):
    """Device idle ms a round while the host was in any phase of
    `ContinuousDecoder.pump` but its sync, or between two rounds: the
    trace's idle gaps inside the traced span, each named by the
    `aiko.decoder.*` span over its middle.  The table by phase goes to
    .bench_out/<cell>/program_spans.json."""
    return regions.device_wait_on_host_ms(run)
