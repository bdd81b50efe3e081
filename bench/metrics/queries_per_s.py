"""Queries answered ``ok`` in the window, over the window."""


def read(run):
    if not run.queries:
        return None
    return sum(q["ok"] for q in run.queries) / run.window_s
