"""Traffic kind "fit": ``paddle.Model.fit`` over a seeded token stream.

Set-up builds ONE ``paddle.Model`` (the hapi compiled train step with its
state), drives it through ``check_steps`` steps with ``Model.fit`` and the
DataLoader, reading what ``correct`` compares, and hands the same object
to the window, which is another ``Model.fit`` call on it.  The window runs
whole optimizer steps until ``--seconds`` have passed and divides all
their tokens by all the time, barrier to barrier (``fit`` reads the loss
back at the end of every step).
"""
import gc
import time

import numpy as np

from benchmark import compare, harness


# Of the gaps that ``gaps_between`` reads, those that ``correct`` holds
# to a limit.  The first step's loss has no upper reading (neither the
# int8 control nor a planted fault moves it beyond what sound runs read)
# and the third's swings a hundredfold from seed to seed with the later
# steps' noise: both are printed, neither is held (PERF.md section 2).
COMPARED = ("loss_gap.step2", "grad_norm_gap", "delta_norm_gap")


def synthetic_tokens(n, S, V, seed):
    """Seeded learnable token stream (copied from ``chip_smoke.py``): a
    skewed unigram distribution with a deterministic successor on every
    other position.  Every row differs."""
    rng = np.random.RandomState(seed % (2 ** 32))
    ranks = np.arange(1, V + 1, dtype=np.float64)
    p = 1.0 / ranks ** 1.1
    ids = rng.choice(V, size=(n, S), p=p / p.sum()).astype(np.int32)
    ids[:, 1::2] = (ids[:, 0::2] * 31 + 7) % V
    return ids


class TimedLoader:
    """The harness-side wrapper around the loader's ``__next__``: sums
    the time ``fit`` waits for a batch, under the span
    ``bench.loader_next``."""

    def __init__(self, loader):
        self.loader, self.wait_s = loader, 0.0

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        it = iter(self.loader)
        while True:
            t0 = time.perf_counter()
            with harness.span("bench.loader_next"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            self.wait_s += time.perf_counter() - t0
            yield batch


def _loader(ids, batch):
    from paddle_tpu.io import DataLoader, Dataset

    class Rows(Dataset):
        def __len__(self):
            return len(ids)

        def __getitem__(self, i):
            return ids[i], ids[i]

    return DataLoader(Rows(), batch_size=batch, shuffle=False,
                      drop_last=True)


def _norms(leaves, start=None):
    """{leaf: norm of the leaf, or of its change from ``start``}."""
    import jax.numpy as jnp
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        (a - start[k] if start else a).astype(jnp.float32))))
        for k, a in leaves.items()}


def leaf_norms(family, named_arrays, start_named=None):
    """{benchmark leaf: float} of {program name: array}, one device call;
    with ``start_named``, of the change from those arrays."""
    import jax
    leaves = family.split_leaves(named_arrays)
    start = family.split_leaves(start_named) if start_named else None
    return {k: float(v) for k, v in
            jax.device_get(jax.jit(_norms)(leaves, start)).items()}


def build(run):
    """The program's side: network with the seeded weights, optimizer,
    ``paddle.Model`` prepared as the mix says."""
    import paddle_tpu as paddle
    t = run.traffic
    net = run.family.build_network(run.model, run.seed)
    opt_spec = dict(t["optimizer"])
    opt_cls = getattr(paddle.optimizer, opt_spec.pop("name"))
    opt = opt_cls(parameters=net.parameters(), **opt_spec)
    model = paddle.Model(net)
    model.prepare(opt, run.family.build_loss(), amp_configs=dict(t["amp"]))
    return model


def check_steps(run, model, ids):
    """Drive ``model`` through the first steps with ``Model.fit`` and
    read what ``correct`` compares: each loss, the per-leaf norm of the
    first gradient from AdamW's first moment after one step
    (m1 = (1 - beta1) g1), and the per-leaf norm of the parameters'
    change after the last."""
    import paddle_tpu as paddle
    t = run.traffic
    B, n = t["batch"], t["check_steps"]
    beta1 = model._optimizer._beta1
    seen = {"loss": []}

    class Probe(paddle.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            seen["loss"].append(float(logs["loss"]))
            if step == 0:
                opt = model.train_state_dict()["opt"]
                m1 = leaf_norms(run.family,
                                {k: v["moment1"] for k, v in opt.items()})
                seen["grad1_norm"] = {k: v / (1 - beta1)
                                      for k, v in m1.items()}

    model.fit(TimedLoader(_loader(ids[:B * n], B)), epochs=1, verbose=0,
              callbacks=[Probe()])
    named = {k: p._value for k, p in model.network.named_parameters()}
    start = run.family.make_per_layer(run.model, run.seed)
    seen["delta_norm"] = leaf_norms(
        run.family, named, {k: start[run.family.leaf_name(k)] for k in named})
    return seen


def window(run, model, ids):
    """The measured window: one ``Model.fit`` call that ends at the first
    step boundary at or after ``--seconds`` of ``run.tracer.clock()`` (the
    host's clock, less what a traced run's trace write held)."""
    import paddle_tpu as paddle
    t = run.traffic
    B, S = t["batch"], t["seq_len"]
    loader = TimedLoader(_loader(ids, B))
    state = {"steps": 0, "ends": [], "traced_steps": 0, "span": None}

    class Window(paddle.callbacks.Callback):
        def on_train_batch_begin(self, step, logs=None):
            state["span"] = harness.span("bench.fit_step")
            state["span"].__enter__()

        def on_train_batch_end(self, step, logs=None):
            state["span"].__exit__(None, None, None)
            now = run.tracer.clock()
            state["steps"] += 1
            state["ends"].append(now)
            if run.tracer.active:
                state["traced_steps"] += 1
            run.tracer.tick(now - state["t0"])
            if now - state["t0"] >= run.seconds:
                model.stop_training = True

    state["t0"] = run.tracer.clock()
    model.fit(loader, epochs=1_000_000, verbose=0, callbacks=[Window()])
    run.tracer.stop()
    elapsed = state["ends"][-1] - state["t0"]
    tokens = state["steps"] * B * S
    run.obs.update(window_s=elapsed, steps=state["steps"], tokens=tokens,
                   input_wait_s=loader.wait_s,
                   traced_steps=state["traced_steps"],
                   traced_tokens=state["traced_steps"] * B * S,
                   batch=B, seq_len=S)
    return tokens / elapsed


def reference_steps(run, ids, precision="highest", fault=None):
    """The plain reference over the same first steps."""
    t = run.traffic
    B, n = t["batch"], t["check_steps"]
    return run.reference.train_steps(
        run.model, run.family.make_stacked(run.model, run.seed),
        ids[:B * n].reshape(n, B, -1), precision=precision,
        lr=t["optimizer"]["learning_rate"],
        wd=t["optimizer"]["weight_decay"], fault=fault)


def gaps_between(seen, ref):
    """The numbers ``correct`` compares: each step's loss, the first
    gradient's norm and the parameters' change, the last two by the
    worst leaf."""
    gaps = {f"loss_gap.step{i + 1}": compare.relative_gap(p, r)
            for i, (p, r) in enumerate(zip(seen["loss"], ref["loss"]))}
    gaps["grad_norm_gap"], grad_at = compare.worst_leaf_gap(
        seen["grad1_norm"], ref["grad1_norm"])
    flat = compare.flat_gradient_leaves(ref["grad1_norm"])
    gaps["delta_norm_gap"], delta_at = compare.worst_leaf_gap(
        seen["delta_norm"], ref["delta_norm"], skip=flat)
    return gaps, {"worst_grad_leaf": grad_at, "worst_delta_leaf": delta_at,
                  "flat_leaves": sorted(flat)}


def run(run):
    t = run.traffic
    B, S, V = t["batch"], t["seq_len"], run.model["vocab_size"]
    t0 = time.perf_counter()
    rows = B * t["check_steps"] + \
        B * int(np.ceil(run.seconds * t["rows_per_second"] / B))
    ids = synthetic_tokens(rows, S, V, run.seed)
    t1 = time.perf_counter()
    model = build(run)
    t2 = time.perf_counter()
    seen = check_steps(run, model, ids)
    t3 = time.perf_counter()
    run.window_starts(data_s=round(t1 - t0, 3), weights_s=round(t2 - t1, 3),
                      first_steps_s=round(t3 - t2, 3),
                      import_s=round(t0 - run.started, 3))
    tokens_per_s = window(run, model, ids[B * t["check_steps"]:])
    run.window_closed()
    harness.say("window", train_tokens_per_s=tokens_per_s, **run.obs)
    model = None
    gc.collect()
    t4 = time.perf_counter()
    ref = reference_steps(run, ids)
    gaps, where = gaps_between(seen, ref)
    harness.say("reference", seconds=round(time.perf_counter() - t4, 2),
                program_loss=seen["loss"], reference_loss=ref["loss"],
                **gaps, **where)
    for name in COMPARED:
        run.check.at_most(name, gaps[name], run.limits[name.split(".")[0]])
    run.check.at_most("compiles_in_window", run.obs["compiles_in_window"], 0)
    return {"train_tokens_per_s": tokens_per_s}, run.obs["steps"], 0
