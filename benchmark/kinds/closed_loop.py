"""Traffic kind ``closed_loop``: a fixed number of clients against one
``ServingEngine``, each sending its next request the moment its last
stream ends. Greedy decoding, no shared prefixes.

Parameters (the traffic file): ``clients``; ``pairs``, the fixed multiset
of [prompt, output] lengths, written out in the file, which the run's
seed puts in an order; the clients take the next pair of that order in
turn, round and round, so every seed offers the same sizes in another
order whatever the number of clients; ``ramp_s`` before the window, in
which the first ``clients`` requests have their outputs cut to
(n+1)/clients of their length so that the clients are out of phase when
the window opens; ``traced_s`` (how long the ``--trace 1`` run
profiles); ``reference``. At the window's end the streams still running
are cancelled, not drained.

Of the engine only public names are used: ``submit``, ``events``,
``cancel``, ``warmup``, ``start``, ``shutdown``, ``stats``, ``config``,
``attention_impl``, ``ragged_compiles``, ``scheduler.preemptions``.
"""
from __future__ import annotations

import itertools
import math
import threading
import time

import numpy as np

from lib import build, stats
from lib.profile import TracedPart

COUNTERS = ("serving.ragged_steps", "serving.decode_tokens",
            "serving.prefill_tokens")
ENDED_WELL = ("length", "eos")
LOOK_S = 0.5


def make_plan(traffic: dict, seed: int):
    """The fixed multiset of (prompt_len, output_len) pairs in the seed's
    order. Request n of a run, whichever client sends it, has the sizes
    ``plan[n % len(plan)]``."""
    pairs = [(int(p), int(o)) for p, o in traffic["pairs"]]
    order = np.random.default_rng([int(seed), 3]).permutation(len(pairs))
    return [pairs[int(j)] for j in order]


def prompt_tokens(seed: int, nth: int, vocab: int, n: int):
    """Token ids of a run's nth request: distinct streams, so no two
    requests share a prefix."""
    return np.random.default_rng([int(seed), 5, nth]) \
        .integers(0, vocab, n).tolist()


def _client(i, n_clients, plan, turn, cell, eng, vocab, temperature, stop,
            records, lock):
    while not stop.is_set():
        nth = next(turn)               # itertools.count: one at a time
        plen, olen = plan[nth % len(plan)]
        if nth < n_clients:            # the ramp's requests
            olen = max(1, round(olen * (nth + 1) / n_clients))
        prompt = prompt_tokens(cell.seed, nth, vocab, plen)
        rec = {"client": i, "nth": nth, "prompt": prompt, "want": olen,
               "times": [], "tokens": [], "end": None,
               "submit": time.perf_counter()}
        rec["rid"] = eng.submit(prompt, max_new_tokens=olen,
                                temperature=temperature)
        rec["submitted"] = time.perf_counter()
        with lock:
            records.append(rec)
        for kind, val in eng.events(rec["rid"]):
            if kind == "tok":
                rec["times"].append(time.perf_counter())
                rec["tokens"].append(val)
            else:
                rec["end"] = val
        rec["ended"] = time.perf_counter()


def _pages_held(records, block_size, t_open, t_close):
    """Pages of the KV pool that requests in flight hold, every LOOK_S of
    the window, worked out from the clients' records so that nothing
    waits for the engine's lock: a request holds the pages of its prompt
    and one more token from the moment ``submit()`` returned (with a
    slot for every client one is always free, and admission allocates
    the whole prompt), and a page more whenever its next token starts
    one, until its stream ends. Exact to within a step as long as
    nothing is preempted."""
    held = []
    for k in range(int((t_close - t_open) / LOOK_S)):
        t = t_open + (k + 0.5) * LOOK_S
        n = 0
        for r in records:
            if r.get("submitted", math.inf) > t or \
                    r.get("ended", math.inf) <= t:
                continue
            got = sum(1 for x in r["times"] if x <= t)
            n += -(-(len(r["prompt"]) + 1 + got) // block_size)
        held.append(n)
    return held


def _check_reference(cell, model, records, spec):
    """Teacher-force the longest finished requests that fit ``pad_to``
    through the float32 reference, padded to that one length (one
    compile, and the same cost whatever their lengths): every generated
    token within ``margin`` of the reference's best logit. The longest,
    so that prompts of several prefill chunks and attention over many
    pages are what is compared."""
    pad = int(spec["pad_to"])
    done = sorted((r for r in records if r["end"] in ENDED_WELL
                   and len(r["prompt"]) + len(r["tokens"]) <= pad),
                  key=lambda r: (-len(r["prompt"]) - len(r["tokens"]),
                                 r["nth"]))
    done = done[:int(spec["requests"])]
    if not done:
        return None, ["no finished request short enough for the "
                      "reference check"]
    ids = np.zeros((len(done), pad), np.int32)
    for k, r in enumerate(done):
        seq = r["prompt"] + r["tokens"]
        ids[k, :len(seq)] = seq
    lg = np.asarray(cell.reference.logits(build.named_params(model), ids,
                                          cell.config))
    worst, n_tok = 0.0, 0
    for k, r in enumerate(done):
        p = len(r["prompt"])
        rows = lg[k, p - 1:p - 1 + len(r["tokens"])]
        short = rows.max(-1) - rows[np.arange(len(r["tokens"])),
                                    r["tokens"]]
        worst = max(worst, float(short.max()))
        n_tok += len(r["tokens"])
    info = {"requests": len(done), "tokens": n_tok,
            "prompt_and_output": [(len(r["prompt"]), len(r["tokens"]))
                                  for r in done],
            "worst_shortfall": worst, "margin": float(spec["margin"])}
    bad = [] if worst <= float(spec["margin"]) else [
        "a generated token is %.4f under the reference's best logit "
        "(margin %g)" % (worst, float(spec["margin"]))]
    return info, bad


def run(cell) -> dict:
    pt, cfg, tf = cell.pt, cell.config, cell.traffic
    obs = pt.observability
    t0 = time.perf_counter()
    model = build.build_model(pt, cfg, cell.seed, train=False)
    eng = build.build_engine(pt, cfg, model)
    t1 = time.perf_counter()
    eng.warmup()
    cell.log("set-up: model and engine %.2f s, warm-up request (compile or "
             "cache load) %.2f s; attention: %s; token budget %d"
             % (t1 - t0, time.perf_counter() - t1, eng.attention_impl,
                eng.config.token_budget))
    if cell.trace:
        obs.enable()               # spans and counters: traced run only
    plan, n_clients = make_plan(tf, cell.seed), int(tf["clients"])
    stop, lock, records = threading.Event(), threading.Lock(), []
    turn = itertools.count()
    threads = [threading.Thread(
        target=_client, name="client-%d" % i, daemon=True,
        args=(i, n_clients, plan, turn, cell, eng, int(cfg["vocab_size"]),
              float(tf.get("temperature", 0.0)), stop, records, lock))
        for i in range(n_clients)]
    traced = TracedPart() if cell.trace else None
    problems = []

    def counters():
        return {n: obs.registry.counter(n).value for n in COUNTERS} \
            if cell.trace else {}

    def sleep_until(t):
        d = t - time.perf_counter()
        if d > 0:
            time.sleep(d)

    eng.start()
    try:
        t_ramp = time.perf_counter()
        for th in threads:
            th.start()
        sleep_until(t_ramp + float(tf["ramp_s"]))
        cell.open_window()
        t_open, epoch_open, c_open = time.perf_counter(), time.time(), \
            counters()
        t_close = t_open + cell.seconds
        if traced:
            sleep_until(t_open + cell.seconds / 2)
            traced.start()
            time.sleep(float(tf.get("traced_s", 2.0)))
            traced.stop()
        sleep_until(t_close)
        c_close, epoch_close = counters(), time.time()
        cell.close_window()
        at_close = eng.stats()         # waits for the engine's lock
        # end of the window: cancel what still runs, drain nothing
        stop.set()
        with lock:
            running = [r for r in records if r["end"] is None]
        for r in running:
            eng.cancel(r["rid"])
        for th in threads:
            th.join(timeout=30.0)
        if any(th.is_alive() for th in threads):
            problems.append("a client thread did not end")
    finally:
        try:
            eng.shutdown()             # raises if the pool did not drain
        except AssertionError as e:
            problems.append("pool did not drain at shutdown: %s" % e)
    if cell.trace:
        obs.disable()

    in_win = lambda t: t_open <= t < t_close          # noqa: E731
    tokens = sum(in_win(t) for r in records for t in r["times"])
    gaps = [1e3 * (b - a) for r in records
            for a, b in zip(r["times"], r["times"][1:]) if in_win(b)]
    asked = [r for r in records if in_win(r["submit"])]
    ttft = [1e3 * (r["times"][0] - r["submit"]) if r["times"]
            else math.inf for r in asked]
    # the only cancellations are the benchmark's own, at the window's end
    cut = [r for r in records if r["end"] == "cancelled"]
    badly = [r for r in records
             if r["end"] not in ENDED_WELL + ("cancelled",)]
    if badly:
        problems.append("%d stream(s) ended badly: %r"
                        % (len(badly), sorted({r["end"] for r in badly})))
    pages = eng.config.num_blocks
    held = _pages_held(records, eng.config.block_size, t_open, t_close)
    cell.log("kv pool: %d pages of %d tokens; held by requests in flight "
             "(from the clients' records, every %g s of the window): mean "
             "%.1f, most %d; preemptions in the whole run: %d"
             % (pages, eng.config.block_size, LOOK_S,
                sum(held) / len(held), max(held),
                eng.scheduler.preemptions))
    cell.log("engine.stats() after the window: %s"
             % {"pages_in_use": at_close.total_blocks - at_close.free_blocks,
                "running": at_close.running,
                "prefilling": at_close.prefilling,
                "waiting": at_close.queue_depth,
                "slots": at_close.max_slots})
    if eng.ragged_compiles != 1:
        problems.append("the ragged step compiled %d times"
                        % eng.ragged_compiles)
    if not gaps or not ttft or math.isinf(stats.median(ttft)):
        problems.append("too few tokens or first tokens in the window")
        gaps, ttft = gaps or [math.inf], ttft or [math.inf]
    t0 = time.perf_counter()
    info, bad = _check_reference(cell, model, records, tf["reference"])
    problems += bad
    cell.log("reference_check: %s (%.2f s)"
             % (info, time.perf_counter() - t0))
    finished = [r for r in records if r["end"] in ENDED_WELL]
    cell.log("streams: %s" % {
        "submitted": len(records), "finished": len(finished),
        "cut_at_the_end_by_the_benchmark": len(cut),
        "ended_badly": len(badly),
        "submitted_in_window": len(asked),
        "finished_in_window": sum(in_win(r["times"][-1])
                                  for r in finished)})
    cell.log("ttft_ms: %s" % stats.summary(ttft))
    # the benchmark's own span around the call into the engine: how long
    # submit() itself took (it waits for the engine's lock)
    submit_ms = [1e3 * (r["submitted"] - r["submit"]) for r in asked
                 if "submitted" in r]
    cell.log("submit_ms: %s" % stats.summary(submit_ms))
    cell.log("itl_ms: %s" % stats.summary(gaps))

    spans = []
    if cell.trace:
        lo, hi = epoch_open * 1e6, epoch_close * 1e6
        spans = [{"name": s.name, "ts": s.ts, "dur": s.dur,
                  "args": dict(s.args)}
                 for s in obs.tracing.finished_spans()
                 if lo <= s.ts < hi]
    return {
        "end_to_end": {"serve_tokens_per_s": tokens / cell.seconds,
                       "itl_ms_p95": stats.percentile(gaps, 95.0)},
        "attempted": len(asked),
        "failed": sum(in_win(r["submit"]) for r in badly),
        "problems": problems,
        "host": {"ttft_ms": ttft, "itl_ms": gaps, "submit_ms": submit_ms},
        "pool": {"pages": pages, "held": held},
        "spans": spans,
        "counters": {n: c_close[n] - c_open[n] for n in c_close},
        "trace": traced.reduce() if traced else None,
    }


def rehearse(cell, topo):
    """The engine's one ragged step, lowered for the described chip with
    the pools at their real size. Outside the measured path, and the one
    place that reads the engine's internals (``_w``, ``_kp``, ``_vp``,
    ``_ragged_step``): the program has no public way to its step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from lib import aot

    with aot.as_tpu():
        model = build.build_model(cell.pt, cell.config, cell.seed,
                                  train=False)
        eng = build.build_engine(cell.pt, cell.config, model)
        cell.log("engine: attention %s, token budget %d, %d pages of %d"
                 % (eng.attention_impl, eng.config.token_budget,
                    eng.config.num_blocks, eng.config.block_size))
        T, R = eng.config.token_budget, eng.config.max_slots
        i32 = lambda *s: jnp.zeros(s, jnp.int32)           # noqa: E731
        f32 = lambda *s: jnp.zeros(s, jnp.float32)         # noqa: E731
        args = (eng._w, i32(T), i32(T), i32(T), i32(R), i32(R), i32(R),
                eng._kp, eng._vp, i32(R, eng.pages_per_seq), f32(R),
                f32(R), jax.random.PRNGKey(0))
        one = SingleDeviceSharding(topo.devices[0])
        return jax.jit(eng._ragged_step).lower(
            *aot.to_struct(args, lambda a: one)).compile()
