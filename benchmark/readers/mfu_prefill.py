"""The prefill programs' share of the chip's bf16 peak over the traced
window: the FLOPs one prefill program does on average (the family's
``needed_per_program`` over the traced prompts, which counts a prompt
prefilled in pieces as its pieces) times the prefill programs that ran on
the device's module line, over the peak times their device time.  A
family without the count, an untraced run or a window with no prefill
gives nothing."""


def read(run, params):
    need = getattr(run.family, params["needed_per_program"], None)
    if run.trace is None or need is None or \
            not run.obs.get("traced_prompt_lens"):
        return None
    hits = [v for name, v in run.trace["module_seconds"].items()
            if params["module"] in name]
    seconds = sum(sec for sec, _ in hits)
    programs = sum(n for _, n in hits)
    if not seconds:
        return None
    per_program = need(run.model, run.obs)
    if not per_program:
        return None
    return 100 * per_program * programs / (
        seconds * run.chips * run.peaks["bf16_flops_per_s"])
