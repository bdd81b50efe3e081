"""Share (%) of the queries' time in the serving engine spent waiting
for their flush: over every query of the traced window, the start of the
``serve.flush`` span that carried it less the start of its
``serve.mixed`` span, summed, over the end of that flush less the same
start, summed. Read from the program's own spans in the trace's host
plane (a flush names its ``serve.mixed`` by ``mixed`` and carries
``lanes`` queries)."""
from bench.scopes import flush_wait_share, scopes


def read(run):
    sc = scopes(run)
    return None if sc is None else flush_wait_share(sc.host, sc.window)
