"""The decode step's share of its roofline, which is bandwidth: the least
bytes one step must move (``flops.decode_step_min_bytes``: every matmul
weight once and the live keys and values of the active slots, averaged
over the traced steps) over the HBM peak, over the step's device time."""
from benchmark import flops
from benchmark.readers import module_step_ms


def read(run, params):
    step_ms = module_step_ms.read(run, params)
    live = run.obs.get("traced_live_kv_tokens_mean")
    if step_ms is None or live is None:
        return None
    least_s = flops.decode_step_min_bytes(run.model, live) \
        / run.peaks["hbm_bytes_per_s"]
    return 100 * least_s / (step_ms / 1000)
