"""Seconds per whole-graph analytic run: the window over the runs it
completed."""


def read(run):
    if not run.items:
        return None
    return run.window_s / len(run.items)
