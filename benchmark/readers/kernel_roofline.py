"""A kernel's share of its roofline: the operations (or bytes) the
algorithm needs in the traced steps, over the peak times the summed
device time of the kernel's events.  ``patterns`` are substrings of the
device operations' names; ``needed`` names the function of the
configuration's family that counts one step's need from ``run.model``
and ``run.obs``; ``bound`` says which roof applies."""


def read(run, params):
    if run.trace is None or not run.obs.get("traced_steps"):
        return None
    seconds = sum(sec for name, sec in run.trace["op_seconds"].items()
                  if any(p in name for p in params["patterns"]))
    if not seconds:
        return None
    need = getattr(run.family, params["needed"])(run.model, run.obs)
    need *= run.obs["traced_steps"]
    return 100 * need / (run.peaks[params["peak"]] * seconds * run.chips)
