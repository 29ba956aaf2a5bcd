"""``scale * num / den`` of two readings, each addressed as ``obs.<key>``
(a counter or span total of the driver) or ``trace.<key>`` (the trace
reduction).  ``den`` may be left out.  Nothing to read gives nothing."""


def lookup(run, address):
    source, key = address.split(".", 1)
    table = run.obs if source == "obs" else run.trace
    if source not in ("obs", "trace") or table is None:
        return None
    return table.get(key)


def read(run, params):
    num = lookup(run, params["num"])
    den = lookup(run, params["den"]) if "den" in params else 1
    if num is None or not den:
        return None
    return params.get("scale", 1) * num / den
