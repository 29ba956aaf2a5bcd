"""Device time of one compiled program per step it ran: the summed
durations of the program's events on the device's module line, over the
steps the driver counted in the traced window."""
from benchmark.readers import ratio


def module_seconds(run, pattern):
    if run.trace is None:
        return None
    hits = [sec for name, (sec, _) in run.trace["module_seconds"].items()
            if pattern in name]
    return sum(hits) if hits else None


def read(run, params):
    seconds = module_seconds(run, params["module"])
    steps = ratio.lookup(run, params["steps"])
    if not seconds or not steps:
        return None
    return 1000 * seconds / steps
