"""The whole training step's share of the chips' bf16 peak: FLOPs the
forward and backward need per token (the family's
``train_flops_per_token``) times the tokens per second of the traced
window, over chips x peak."""


def read(run, params):
    if run.trace is None or not run.obs.get("traced_tokens"):
        return None
    per_token = run.family.train_flops_per_token(run.model, run.obs)
    rate = run.obs["traced_tokens"] / run.trace["window_s"]
    return 100 * per_token * rate / (run.chips * run.peaks["bf16_flops_per_s"])
