"""The serving engine's share of the chip's bf16 peak over the traced
window: ``flops.serve_flops`` of every prompt prefilled and every token
decoded in it, over window x chips x peak."""
from benchmark import flops


def read(run, params):
    if run.trace is None or "traced_prompt_lens" not in run.obs:
        return None
    need = flops.serve_flops(run.model, run.obs["traced_prompt_lens"],
                             run.obs["traced_decode_positions"])
    if not need:
        return None
    return 100 * need / (run.trace["window_s"] * run.chips
                         * run.peaks["bf16_flops_per_s"])
