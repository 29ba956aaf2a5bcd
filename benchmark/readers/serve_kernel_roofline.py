"""A serving kernel's share of its roofline over the traced window: the
least SECONDS the window's work of that kind needs (``needed_seconds``
names a function of the configuration's family, ``f(model, obs, peaks)``,
which weighs compute-bound work by the bf16 peak and bandwidth-bound work
by the HBM peak) over the summed device time of the events whose names
hold one of ``patterns``.  The serving twin of ``kernel_roofline``, which
counts per training step.  A program without such events, a family
without the function, or an untraced run gives nothing."""


def read(run, params):
    need = getattr(run.family, params["needed_seconds"], None)
    if run.trace is None or need is None or \
            "traced_prompt_lens" not in run.obs:
        return None
    seconds = sum(sec for name, sec in run.trace["op_seconds"].items()
                  if any(p in name for p in params["patterns"]))
    if not seconds:
        return None
    least = need(run.model, run.obs, run.peaks)
    if not least:
        return None
    return 100 * least / (seconds * run.chips)
