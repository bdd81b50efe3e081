"""Seconds from process start to the start of the window: generating
the graph, the program's build and transfer, compiling or loading the
compiled programs, and the warm-up."""


def read(run):
    return run.setup_s
