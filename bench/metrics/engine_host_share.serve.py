"""Share of the window (%) in which the serving engine was not inside a
program call: its queues, the host copy of each answer, the guardrail and its
bookkeeping. The program calls are timed by the benchmark's runner
around the engine's own single-device runner, fenced on the device;
the engine copies the answer to the host after the runner returns."""


def read(run):
    calls = [(a, b) for name, a, b in run.spans if name == "runner"
             and run.window_t0 <= a and b <= run.window_t1]
    if not calls:
        return None
    inside = sum(b - a for a, b in calls)
    return 100.0 * (1.0 - inside / run.window_s)
