"""Traversed edges per second, as Graph500 counts them: for every
traversal completed in the window, the undirected edges of its source's
connected component, over the window. The component sizes come from
the reference's own graph, not from the program."""


def read(run):
    srcs = [s for it in run.items for s in it.get("sources", ())]
    if not srcs:
        return None
    ce = run.component_edges()
    return float(sum(ce[int(s)] for s in srcs)) / run.window_s
