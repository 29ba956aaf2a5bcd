"""Traffic kind "serve_open": an open loop of independent users against
one ``ServingEngine``.

The driver steps the engine itself: it submits each request when it falls
due, then calls ``step()``; it times every request from when it was DUE
and reports how late the generator ran.  Arrivals stop at ``--seconds``;
the engine then drains so that every due request gets its latencies, but
tokens and time after the window count for nothing.

Every time of the window is read from ``run.tracer.clock()``, which
stands still while the profiler's stop writes a traced run's trace from
inside the loop: that time is the benchmark's own and no request's, so
the requests not yet due still arrive at their own spacing, and the close
and the drain move with them.
"""
import gc
import time

import numpy as np

from benchmark import arrivals, harness


class Client:
    """One request as its user sees it: when each token arrived."""

    def __init__(self, request):
        self.request = request
        self.handle = None
        self.first = self.last = None
        self.count = self.in_window = 0
        self.done = False


def build(run, net=None):
    """The program's side: the network with the seeded weights (``net``,
    where one is handed in, gets the seed's weights) behind a
    ``ServingEngine`` built as the mix says."""
    from paddle_tpu.inference import ServingEngine
    if net is None:
        net = run.family.build_network(run.model, run.seed)
    else:
        run.family.put_weights(net, run.model, run.seed)
    net.eval()
    return net, ServingEngine(net, **run.traffic["engine"])


def warm_up(engine, schedule):
    """One request through every prefill bucket the schedule draws and
    through the decode chunk, then the engine is reset."""
    by_bucket = {}
    for r in schedule:
        bucket = min(b for b in engine.buckets if b >= len(r.prompt))
        by_bucket.setdefault(bucket, r)
    for r in by_bucket.values():
        engine.submit(r.prompt, max_new_tokens=2)
    while engine.scheduler.has_work:
        engine.step()
    engine.reset()
    return sorted(by_bucket)


def window(run, engine, schedule):
    clients = [Client(r) for r in schedule]
    traced = {"chunks": 0, "prompt_lens": [], "positions": []}
    state = {"close": None, "done": 0}
    backlog = []       # (elapsed, requests submitted and not yet finished)
    clock = run.tracer.clock

    def on_token(client):
        def callback(req, token, last):
            now = clock()
            if client.first is None:
                client.first = now
                if run.tracer.active:
                    traced["prompt_lens"].append(len(client.request.prompt))
            elif run.tracer.active:
                traced["positions"].append(
                    len(client.request.prompt) + client.count)
            client.count += 1
            client.last = now
            if now <= state["close"]:
                client.in_window += 1
            if last and not client.done:
                client.done = True
                state["done"] += 1
        return callback

    late, nxt, n = [], 0, len(clients)
    t0 = clock()
    state["close"] = t0 + run.seconds
    give_up = state["close"] + run.traffic["drain_s"]
    while True:
        now = clock()
        run.tracer.tick(now - t0)
        with harness.span("bench.arrivals"):
            while nxt < n and t0 + clients[nxt].request.due_s <= now:
                c = clients[nxt]
                late.append(clock() - (t0 + c.request.due_s))
                c.handle = engine.submit(c.request.prompt,
                                         c.request.max_new_tokens,
                                         callback=on_token(c))
                nxt += 1
        if engine.scheduler.has_work:
            before = engine.stats["chunks"]
            with harness.span("bench.engine_step"):
                engine.step()
            if run.tracer.active:
                traced["chunks"] += engine.stats["chunks"] - before
            backlog.append((clock() - t0, nxt - state["done"]))
        elif nxt == n:
            break
        else:
            time.sleep(max(0.0, min(
                t0 + clients[nxt].request.due_s - clock(), 0.05)))
        if clock() > give_up:
            break
    run.tracer.stop()
    for name, at in (("backlog_mid", run.seconds / 2),
                     ("backlog_end", run.seconds)):
        run.obs[name] = min(backlog, key=lambda b: abs(b[0] - at))[1] \
            if backlog else 0
    return clients, traced, late, t0


def summarise(run, engine, clients, traced, late, t0):
    n = len(clients)
    close = t0 + run.seconds
    gave_up = close + run.traffic["drain_s"]
    failed = sum(1 for c in clients if not c.done)
    ttft = [((c.first if c.first else gave_up) - (t0 + c.request.due_s)) * 1e3
            for c in clients]
    tpot = [(c.last - c.first) / (c.count - 1) * 1e3 if c.done
            else (gave_up - t0) * 1e3
            for c in clients if c.count > 1 or not c.done]
    tokens_in_window = sum(c.in_window for c in clients)
    decode_steps = traced["chunks"] * engine.chunk
    run.obs.update(
        window_s=run.seconds, requests=n, failed=failed,
        tokens_in_window=tokens_in_window,
        tokens_total=sum(c.count for c in clients),
        drained_s=max(c.last or gave_up for c in clients) - close,
        traced_decode_steps=decode_steps,
        traced_prompt_lens=traced["prompt_lens"],
        traced_decode_positions=traced["positions"],
        # the engine's counters: a ``ratio`` metric reads obs.engine.<key>
        **{f"engine.{k}": v for k, v in engine.stats.items()
           if isinstance(v, (int, float))})
    if decode_steps:
        run.obs["traced_live_kv_tokens_mean"] = \
            sum(traced["positions"]) / decode_steps
    harness.say("latencies", ttft_ms=[round(x, 1) for x in ttft],
                late_ms=[round(1e3 * x, 1) for x in late])
    harness.say("generator", requests=n,
                late_ms_mean=1e3 * sum(late) / len(late),
                late_ms_max=1e3 * max(late),
                ttft_p50_ms=arrivals.percentile(ttft, 50),
                tpot_p50_ms=arrivals.percentile(tpot, 50))
    return {"serve_tokens_per_s": tokens_in_window / run.seconds,
            "ttft_p90_ms": arrivals.percentile(ttft, 90),
            "tpot_p90_ms": arrivals.percentile(tpot, 90)}, failed


def sample(run, clients):
    """The requests whose served tokens are compared: the longest one
    (prompt + output) and ``check_requests`` more drawn from the seed."""
    finished = [c for c in clients if c.done]
    if not finished:
        return []
    longest = max(finished, key=lambda c: len(c.request.prompt) + c.count)
    rng = np.random.RandomState((run.seed + 1) % (2 ** 32))
    rest = [c for c in finished if c is not longest]
    k = min(run.traffic["check_requests"], len(rest))
    picks = [rest[i] for i in rng.choice(len(rest), size=k, replace=False)]
    return [(c.request.prompt, np.asarray(c.handle.tokens, np.int32))
            for c in [longest] + picks]


def padded_length(traffic):
    """One shape for every reference row: the mix's longest prompt plus
    longest output, rounded up to a multiple of 128."""
    longest = sum(spec["max"] if "max" in spec else spec["value"]
                  for spec in (traffic["prompt_len"], traffic["output_len"]))
    return -(-longest // 128) * 128


def compare_with_reference(run, samples, control=None):
    """How far each served token's logit lies below the reference's best
    at its position, over every served token of the sample: the gaps'
    mean (what ``correct`` holds to a limit) and the widest.  With
    ``control`` (a lower precision) the tokens judged are those that
    precision puts first at the same positions, in the program's place."""
    reference = run.reference
    w = run.family.make_stacked(run.model, run.seed)
    T = padded_length(run.traffic)
    gaps = []
    for prompt, served in samples:
        row = np.zeros(T, np.int32)
        full = np.concatenate([prompt, served])
        row[:len(full)] = full
        first, last = len(prompt) - 1, len(full) - 1   # positions judged
        if control:
            targets = reference.best_next_tokens(run.model, w, row, control)
        else:
            targets = np.zeros(T, np.int32)
            targets[first:last] = served
        gaps.append(np.asarray(reference.next_token_gaps(
            run.model, w, row, targets))[first:last])
    gaps = np.concatenate(gaps)
    return {"token_gap_mean": float(gaps.mean()),
            "token_gap_widest": float(gaps.max()),
            "tokens_off_best": int((gaps > 0).sum()), "tokens": len(gaps)}


def run(run):
    t0 = time.perf_counter()
    schedule = arrivals.schedule(run.traffic, run.seed, run.seconds,
                                 run.model["vocab_size"])
    net, engine = build(run)
    t1 = time.perf_counter()
    buckets = warm_up(engine, schedule)
    t2 = time.perf_counter()
    run.window_starts(import_s=round(t0 - run.started, 3),
                      weights_and_engine_s=round(t1 - t0, 3),
                      warm_up_s=round(t2 - t1, 3), buckets=buckets)
    clients, traced, late, w0 = window(run, engine, schedule)
    run.window_closed()
    end_to_end, failed = summarise(run, engine, clients, traced, late, w0)
    harness.say("window", **end_to_end,
                **{k: v for k, v in run.obs.items()
                   if not isinstance(v, list)})
    samples = sample(run, clients)
    net = engine = None
    for c in clients:
        c.handle = None
    gc.collect()
    t3 = time.perf_counter()
    gaps = compare_with_reference(run, samples)
    harness.say("reference", seconds=round(time.perf_counter() - t3, 2),
                requests=len(samples), **gaps)
    run.check.at_most("token_gap_mean", gaps["token_gap_mean"],
                      run.limits["token_gap_mean"])
    run.check.at_most("requests_unanswered", failed, 0)
    run.check.at_most("compiles_in_window", run.obs["compiles_in_window"], 0)
    return end_to_end, len(clients), failed
