"""Share (%) of the traced window in which no operation ran on the
device (profiler trace: one minus the union of device operations over
the window)."""
from bench.trace import idle_share as read  # noqa: F401
