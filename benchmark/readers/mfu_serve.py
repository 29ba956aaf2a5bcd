"""The serving engine's share of the chip's bf16 peak over the traced
window: the family's ``serve_flops`` of every prompt prefilled and every
token decoded in it, over window x chips x peak."""


def read(run, params):
    if run.trace is None or "traced_prompt_lens" not in run.obs:
        return None
    need = run.family.serve_flops(run.model, run.obs)
    if not need:
        return None
    return 100 * need / (run.trace["window_s"] * run.chips
                         * run.peaks["bf16_flops_per_s"])
