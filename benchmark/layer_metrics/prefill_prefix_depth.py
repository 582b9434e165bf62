from benchmark import program_journeys


def read(run):
    """The positions of the pool that a prefill piece's rows read before
    their own, the mean over the pieces dispatched in the traced span:
    the covariate of `prefill_device_ms_per_ktok.*`, whose pieces cost
    more the deeper their prefix.  It is a property of the traffic and
    the chunk size, read to judge that metric by: its direction
    (`better` in the manifest, which every entry has to give) means
    nothing."""
    return program_journeys.prefix_depth(run)
