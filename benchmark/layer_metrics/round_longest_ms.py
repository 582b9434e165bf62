from benchmark import program_rounds


def read(run):
    """The window's longest round with the gap before it, ms (the
    ring's `gap_s` + `wall_s`, rounds after an idle decoder left out):
    100-150 in a run that did not stall."""
    return program_rounds.longest_ms(run)
