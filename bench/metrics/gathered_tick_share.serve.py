def read(run):
    """Slot-pool executor: the share of the window's decode ticks that ran
    the gathered dispatch (whole-pool gather and scatter around the
    decode) rather than decoding the full pool in place, from the
    program's ServingStats counters."""
    gathered = [getattr(s, "gathered_ticks", None) for s in (run.stats0, run.stats1)]
    ticks = run.stats1.ticks - run.stats0.ticks
    if None in gathered or not ticks:
        return None
    return 100.0 * (gathered[1] - gathered[0]) / ticks
