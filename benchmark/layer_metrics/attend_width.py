import statistics

from benchmark import program_rounds


def read(run):
    """The positions a round's decode step built its views and attended
    at, the mean over the window's rounds that ran a step.  The decoder
    picks the width every round from a short ladder, the smallest that
    covers the longest live context (`ContinuousDecoder._attend_width`),
    and leaves it behind the fields of PR 24 in the round's record
    (`observe.profiler.ROUND_RECORD`); `program_rounds.rounds` cuts the
    ring to the window and hands out those fields only, so the width is
    fetched from the ring by the round's `seq`.  None where the program
    keeps no such field (the parent of the PR that added it)."""
    stepped = {r["seq"] for r in program_rounds.rounds(run) or ()
               if r["num_steps"]}
    try:
        from aiko_services_tpu.observe import profiler
        at = profiler.ROUND_RECORD.index("attend_width")
        log = profiler.round_log(program_rounds.DECODER)
    except (ImportError, AttributeError, LookupError, ValueError):
        return None
    widths = [record[at] for record in log
              if record[0] in stepped and len(record) > at]
    return statistics.fmean(widths) if widths else None
