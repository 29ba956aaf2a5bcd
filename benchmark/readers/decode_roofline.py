"""The decode step's share of its roofline, which is bandwidth: the least
bytes one step must move (the family's ``decode_step_min_bytes``: for a
dense model every matmul weight once and the live cache of the active
slots, averaged over the traced steps) over the HBM peak, over the
step's device time."""
from benchmark.readers import module_step_ms


def read(run, params):
    step_ms = module_step_ms.read(run, params)
    if step_ms is None or "traced_live_kv_tokens_mean" not in run.obs:
        return None
    least_s = run.family.decode_step_min_bytes(run.model, run.obs) \
        / run.peaks["hbm_bytes_per_s"]
    return 100 * least_s / (step_ms / 1000)
