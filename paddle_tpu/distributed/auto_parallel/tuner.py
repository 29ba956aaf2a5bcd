"""Auto-sharding tuner v1 (VERDICT r4 #7).

Reference: the auto-parallel cost model + tuner that search the
placement space (python/paddle/distributed/auto_parallel/static/cost/
and tuner/, SURVEY §2.2 auto-parallel row).  The reference costs
per-op distributed programs; here GSPMD owns partitioning, so the
search space is just the mesh factorization (dp, sharding, mp, pp) and
v1 costs each candidate with closed-form memory + communication models
of a transformer-shaped workload.

Per-device MEMORY (bytes), for P params, L layers, hidden H, batch B,
seq S, vocab V, Adam-style optimizer.  The sharding axis is DATA
parallel (ZeRO shards states over replicas), so activations divide by
dp*sh.  Activations assume per-layer remat (the framework's recompute
is standard at the scales where the tuner matters): stored = layer
inputs (2H bytes/token/layer) + one layer's working set:
  params     2P / (mp*pp) / (sh if stage==3 else 1)       (bf16 compute)
  grads      4P / (mp*pp) / (sh if stage>=2 else 1)       (fp32)
  optimizer 12P / (mp*pp) / (sh if stage>=1 else 1)       (fp32 m/v/master)
  acts       tok*(2H*(L/pp) + A_WORK*H),  tok = B*S/(dp*sh)
  logits     2*tok*V/mp * LOGITS_LIVE  (fwd act + bwd dlogits; under pp
             only the last stage holds it, for 1/n_micro of the batch)

Per-step COMMUNICATION time (bytes / ICI_BW), ring-collective factors:
  dp grad sync       2 * 4P/(mp*pp*max(sh,1)) * (dp-1)/dp
  sharding s>=2      same reduce-scatter+allgather volume as dp (folded
                     into the dp term via the flat data axis)
  sharding s==3      + 2 * 2P/(mp*pp) * (sh-1)/sh   (param allgather f+b)
  mp                 (L/pp) * 4 * 2 * 2*(B/dp)*S*H * (mp-1)/mp
  pp                 2 * 2*(B/dp)*S*H   (boundary sends, all micros)
COMPUTE time: 6*P*B*S tokens-flops / (n_devices * PEAK * EFF), with the
pipeline bubble multiplier (1 + (pp-1)/n_micro).

cost = compute*bubble + comm (no-overlap, conservative).  Feasible =
memory <= budget.  Among feasible candidates the lowest cost wins; ties
break toward plain dp (fewer axes, simpler program).
"""
from dataclasses import dataclass, field

from ...device import chip as _chip

__all__ = ["ModelStats", "estimate", "tune"]

# planning constants for the target chip (v5e) — tunable via
# estimate()/tune() kwargs; the peak is the peaks table's row
ICI_BW = 90e9          # bytes/s per device, ring all-reduce effective
PEAK = _chip.peaks(_chip.V5E).bf16_flops
EFF = 0.45             # sustained fraction of peak for a train step
A_WORK = 30.0          # one layer's live working set, bytes/token/H
LOGITS_LIVE = 2.0      # fwd logits + bwd dlogits live together


@dataclass
class ModelStats:
    n_params: int
    n_layers: int
    hidden: int
    n_heads: int
    vocab: int
    batch: int
    seq: int

    @classmethod
    def from_config(cls, cfg, batch, seq=None):
        """From a GPTConfig-shaped object (hidden_size,
        num_hidden_layers, num_attention_heads, vocab_size)."""
        H = cfg.hidden_size
        L = cfg.num_hidden_layers
        V = cfg.vocab_size
        S = seq or getattr(cfg, "max_position_embeddings", 1024)
        n_params = V * H + S * H + L * 12 * H * H + 2 * H
        return cls(n_params=n_params, n_layers=L, hidden=H,
                   n_heads=cfg.num_attention_heads, vocab=V,
                   batch=batch, seq=S)

    @classmethod
    def from_layer(cls, net, batch, seq):
        """Heuristic extraction from a Layer: exact param count; layer
        count from repeated block types; hidden/vocab from the largest
        embedding-shaped parameter."""
        import numpy as np
        params = [p for _, p in net.named_parameters()]
        n_params = int(sum(int(np.prod(p.shape)) for p in params))
        from collections import Counter
        kinds = Counter(type(s).__name__ for s in net.sublayers())
        # the most-repeated composite block is "the layer"
        L = max([c for n, c in kinds.items()
                 if c > 1 and ("Layer" in n or "Block" in n
                               or "Decoder" in n or "Encoder" in n)],
                default=1)
        two_d = [tuple(p.shape) for p in params if len(p.shape) == 2]
        vocab, hidden = max(two_d, key=lambda s: s[0] * s[1],
                            default=(1, 1))
        if vocab < hidden:
            vocab, hidden = hidden, vocab
        heads = max(hidden // 64, 1)
        return cls(n_params=n_params, n_layers=L, hidden=hidden,
                   n_heads=heads, vocab=vocab, batch=batch, seq=seq)


def estimate(st, dp, sh, mp, pp, *, stage=2, n_micro=None,
             hbm_bytes=16e9, ici_bw=ICI_BW, peak=PEAK, eff=EFF):
    """Cost one (dp, sharding, mp, pp) candidate; returns a dict with
    mem_bytes, comm_s, compute_s, cost_s, feasible."""
    P, L, H, V = st.n_params, st.n_layers, st.hidden, st.vocab
    B, S = st.batch, st.seq
    n = dp * sh * mp * pp
    n_micro = n_micro or max(pp, 1)

    p_b = 2.0 * P / (mp * pp) / (sh if stage == 3 else 1)
    g_b = 4.0 * P / (mp * pp) / (sh if stage >= 2 else 1)
    o_b = 12.0 * P / (mp * pp) / (sh if stage >= 1 else 1)
    tok = B * S / (dp * sh)
    # remat assumed: layer inputs + one working set; 1F1B keeps pp
    # microbatch boundary inputs in flight per stage
    micro_tok = tok / (n_micro if pp > 1 else 1)
    act = micro_tok * (2.0 * H * (L / pp) * (pp if pp > 1 else 1)
                       + A_WORK * H / mp)
    logits = 2.0 * micro_tok * V / mp * LOGITS_LIVE
    mem = p_b + g_b + o_b + act + logits

    flat_data = dp * sh           # dp and sharding share the grad axis
    comm = 0.0
    if flat_data > 1:
        comm += 2.0 * (4.0 * P / (mp * pp)) / flat_data \
            * (flat_data - 1)
    if stage == 3 and sh > 1:
        comm += 2.0 * (2.0 * P / (mp * pp)) * (sh - 1) / sh
    # activation traffic scales with this device's tokens: the batch
    # splits across BOTH data axes (dp and ZeRO sharding)
    if mp > 1:
        comm += (L / pp) * 4 * 2 * (2.0 * tok * H) * (mp - 1) / mp
    if pp > 1:
        comm += 2 * (2.0 * tok * H)
    comm_s = comm / ici_bw

    compute_s = 6.0 * P * B * S / (n * peak * eff)
    bubble = 1.0 + (pp - 1) / max(n_micro, 1)
    cost = compute_s * bubble + comm_s
    return {"dp": dp, "sharding": sh, "mp": mp, "pp": pp,
            "stage": stage if sh > 1 else 0,
            "mem_bytes": mem, "mem_gb": round(mem / 1e9, 2),
            "comm_s": comm_s, "compute_s": compute_s,
            "bubble": bubble, "cost_s": cost,
            "feasible": mem <= hbm_bytes * 0.92}


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def tune(st, n_devices, *, allow_mp=True, allow_pp=True,
         allow_sharding=True, stage=2, hbm_gb=16.0, n_micro=None,
         ici_bw=ICI_BW, peak=PEAK, eff=EFF):
    """Search mesh factorizations of ``n_devices``; returns
    (best, report) where report lists every evaluated candidate sorted
    by cost (infeasible ones at the end).

    Constraints: mp must divide the head count, pp must divide the
    layer count, dp must divide the batch.  If nothing is feasible the
    lowest-memory candidate is returned with feasible=False so the
    caller can see how far over budget the model is.
    """
    hbm = hbm_gb * 1e9
    report = []
    for mp in (_divisors(n_devices) if allow_mp else [1]):
        if st.n_heads % mp or mp > st.n_heads:
            continue
        for pp in (_divisors(n_devices // mp) if allow_pp else [1]):
            if st.n_layers % pp:
                continue
            rest = n_devices // (mp * pp)
            for sh in (_divisors(rest) if allow_sharding else [1]):
                dp = rest // sh
                # the batch splits across both data axes; under pp it
                # must also split into whole microbatches
                data = dp * sh
                if st.batch % data:
                    continue
                if pp > 1 and st.batch % (data * (n_micro or pp)):
                    continue
                report.append(estimate(
                    st, dp, sh, mp, pp, stage=stage, n_micro=n_micro,
                    hbm_bytes=hbm, ici_bw=ici_bw, peak=peak, eff=eff))
    if not report:
        raise ValueError(
            f"tune: no mesh factorization of {n_devices} devices "
            f"satisfies the divisibility constraints (heads="
            f"{st.n_heads}, layers={st.n_layers}, batch={st.batch})")
    # prefer: feasible, lowest cost, then fewest parallel axes
    def key(c):
        axes = sum(1 for a in ("dp", "sharding", "mp", "pp")
                   if c[a] > 1)
        return (not c["feasible"], c["cost_s"], axes)
    report.sort(key=key)
    best = report[0] if report[0]["feasible"] else \
        min(report, key=lambda c: c["mem_bytes"])
    return best, report
