"""The arithmetic of ``correct``: gaps between what the timed path
produced and what the plain reference gives.  No limit lives here; each
cell's limits are data (``limits/<cell>.json``), set from chip readings
as PERF.md records."""
import statistics


def relative_gap(value, reference):
    return abs(value - reference) / abs(reference)


def worst_leaf_gap(program, reference, skip=()):
    """The largest, over the leaves, of the gap between the program's
    norm and the reference's, measured against the reference's norm of
    that leaf or of the median leaf, whichever is larger.  Returns
    (gap, leaf)."""
    if set(program) != set(reference):
        raise ValueError("program and reference have different leaves: "
                         f"{sorted(set(program) ^ set(reference))[:6]}")
    median = statistics.median(reference.values())
    worst, at = 0.0, None
    for leaf, ref in reference.items():
        if leaf in skip:
            continue
        gap = abs(program[leaf] - ref) / max(ref, median)
        if not gap <= worst:       # a NaN gap wins
            worst, at = gap, leaf
    return worst, at


def flat_gradient_leaves(reference_grad_norms, share=1e-3):
    """Leaves whose reference gradient is nought to rounding (under
    ``share`` of the median leaf's): under Adam they move by round-off
    alone, so their change is not compared."""
    median = statistics.median(reference_grad_norms.values())
    return {leaf for leaf, g in reference_grad_norms.items()
            if g < share * median}
