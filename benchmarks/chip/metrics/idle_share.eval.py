"""Device idle share of the eval window, %: 1 - (union of the intervals
in which an operation ran on the device) / window, from the profiler
trace."""


def read(run):
    r = run.reduced
    if run.loop != "eval" or r is None or not r.devices:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
