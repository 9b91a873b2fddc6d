"""Traffic kind ``train_stream``: fresh token batches from the seed, fed
to ``TrainStep.run_steps_stream(n, ...)`` one dispatch after another,
each dispatch ended by ``float(loss)``.

Parameters (the traffic file): ``batch`` (global), ``seq``,
``steps_per_dispatch``, ``traced_dispatches`` (how many dispatches the
``--trace 1`` run profiles), ``reference.tolerance``.
"""
from __future__ import annotations

import math
import time

import numpy as np

from lib import build, flops, stats
from lib.profile import TracedPart


def _batches(seed, vocab, n, batch, seq):
    """Endless (ids, labels) pairs of shape [n, batch, seq], int32, from
    the seed. Labels are independent random tokens: the loss stays near
    ln(vocab) and the work per step is the same for every seed."""
    rng = np.random.default_rng([int(seed), 7])
    while True:
        yield (rng.integers(0, vocab, (n, batch, seq), dtype=np.int32),
               rng.integers(0, vocab, (n, batch, seq), dtype=np.int32))


def _memory_analysis(cell, step, n, pair):
    """The compiled dispatch's own account of its memory (the allocator's
    high-water mark misses a running program's temporaries): arguments +
    outputs + temporaries - aliased, in bytes on one device. Traced run
    only, outside the window. ``TrainStep`` has no public way to the
    program of ``run_steps_stream``, so this reads its internals, and
    says so and returns None once they have moved: the run stays whole
    and the metric that reads this is left out."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    try:
        jitted = step._multi_jitted[("stream", n)]
        batch = step._prepare_batch(pair, leading_steps=n)
    except (AttributeError, KeyError, TypeError) as e:
        cell.log("memory_analysis: not read, TrainStep's internals have "
                 "moved (%s: %s)" % (type(e).__name__, e))
        return None
    ma = jitted.lower(
        jnp.zeros((n, 2), jnp.uint32), jnp.zeros(n, jnp.float32),
        tuple(step.param_arrays), step.opt_state, *batch
    ).compile().memory_analysis()
    cell.log("memory_analysis: %s (%.1f s)"
             % ({"argument": ma.argument_size_in_bytes,
                 "output": ma.output_size_in_bytes,
                 "temp": ma.temp_size_in_bytes,
                 "alias": ma.alias_size_in_bytes},
                time.perf_counter() - t0))
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def run(cell) -> dict:
    pt, cfg, tf = cell.pt, cell.config, cell.traffic
    n, batch, seq = (int(tf[k]) for k in
                     ("steps_per_dispatch", "batch", "seq"))
    t0 = time.perf_counter()
    model = build.build_model(pt, cfg, cell.seed, train=True)
    step = build.build_train_step(pt, cfg, model, cell.devices)
    n_params = build.count_params(model)
    cell.log("set-up: model, optimizer and TrainStep %.2f s; %d parameters"
             % (time.perf_counter() - t0, n_params))
    feed = _batches(cell.seed, int(cfg["vocab_size"]), n, batch, seq)

    def dispatch(pair, lrs=None):
        return step.run_steps_stream(n, *pair, lrs=lrs)

    # warm-up: the one program this traffic uses. Twice, because the
    # first call's arguments are fresh arrays and the second's are the
    # first's donated outputs: whatever that difference would compile,
    # it compiles here.
    warm, warm_loss = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        warm_loss.append(float(dispatch(next(feed))))
        warm.append(time.perf_counter() - t0)
    cell.log("set-up: first dispatch (compile or cache load) %.2f s, "
             "second %.2f s" % tuple(warm))

    traced = TracedPart() if cell.trace else None
    trace_at = max(1, int(cell.seconds / 2 / max(warm[1], 1e-3)))
    n_traced = int(tf.get("traced_dispatches", 3))
    samples, losses = [], []
    pair = next(feed)
    cell.open_window()
    t_open = t_last = time.perf_counter()
    while True:
        i = len(samples)
        if traced and i == trace_at:
            traced.start()
            t_last = time.perf_counter()   # start_trace is not a dispatch
        out = dispatch(pair)
        pair = next(feed)              # the host makes the next batch
        losses.append(float(out))      # while the device runs this one
        now = time.perf_counter()
        samples.append(now - t_last)
        t_last = now
        if traced and i == trace_at + n_traced - 1:
            traced.stop()
            t_last = time.perf_counter()   # stop_trace is not a dispatch
        if now - t_open >= cell.seconds:
            break
    elapsed = sum(samples)
    cell.close_window()
    if traced and traced.dir and len(samples) < trace_at + n_traced:
        traced.stop()

    tokens = len(samples) * n * batch * seq
    tps = tokens / elapsed
    fpt = flops.train_flops_per_token(n_params, int(cfg["num_layers"]),
                                      int(cfg["hidden_size"]), seq)
    peak = cell.peaks["bf16_flops"] * cell.chips if cell.peaks else None
    step_ms = [1e3 * s / n for s in samples]
    cell.log("window: %s" % {"dispatches": len(samples),
                             "steps": len(samples) * n, "tokens": tokens,
                             "elapsed_s": elapsed})
    cell.log("step_ms: %s; slowest dispatch is number %d of %d"
             % (stats.summary(step_ms), step_ms.index(max(step_ms)) + 1,
                len(step_ms)))
    if peak:
        cell.log("model_flops_utilization (not a metric; tokens/s times a "
                 "constant): %s" % {k: tps * v / peak
                                    for k, v in fpt.items()})

    # ---- correct, outside the window: one more dispatch with learning
    # rate 0, so the parameters stay as they are and the loss the program
    # reports for its last step is the loss of those parameters on that
    # step's batch; the float32 reference computes the same.
    t0 = time.perf_counter()
    ids, labels = next(feed)
    check = float(dispatch((ids, labels),
                           lrs=np.zeros(n, np.float32)))
    ref = cell.reference.loss(build.named_params(model), ids[-1],
                              labels[-1], cfg)
    tol = float(tf["reference"]["tolerance"])
    cell.log("loss: %s" % {"first": warm_loss[0], "last": losses[-1],
                           "check": check, "reference": ref,
                           "difference": check - ref, "tolerance": tol,
                           "sequences": batch,
                           "check_s": time.perf_counter() - t0})
    problems = []
    if not all(math.isfinite(x) for x in losses + [check, ref]):
        problems.append("non-finite loss")
    if not abs(check - ref) <= tol:
        problems.append("loss %.6f differs from the reference's %.6f by "
                        "more than %g" % (check, ref, tol))

    record = {
        "end_to_end": {"train_tokens_per_s": tps},
        "attempted": len(samples) * n, "failed": 0, "problems": problems,
        "host": {"step_ms": step_ms},
        "trace": traced.reduce() if traced else None,
    }
    if cell.trace:
        record["memory_analysis_bytes"] = _memory_analysis(
            cell, step, n, (ids, labels))
    return record


def rehearse(cell, topo):
    """The dispatch's program, lowered for the described chips: the
    program builds its step as in ``run`` (on host devices), the jit is
    captured in place of running, and its arguments become shapes on the
    described devices. Outside the measured path; it steers the step's
    mesh through its internals (``_mesh``, ``_process_mesh``)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

    from lib import aot

    pt, cfg, tf = cell.pt, cell.config, cell.traffic
    n, batch, seq = (int(tf[k]) for k in
                     ("steps_per_dispatch", "batch", "seq"))
    with aot.as_tpu():
        model = build.build_model(pt, cfg, cell.seed, train=True)
        step = build.build_train_step(pt, cfg, model, cell.devices)
        cell.log("built: %d parameters" % build.count_params(model))
        captured = {}
        feed = _batches(0, int(cfg["vocab_size"]), n, batch, seq)
        with aot.capture_jit(captured):
            try:
                step.run_steps_stream(n, *next(feed))
            except aot.Stop:
                pass
        if step._mesh is None:
            one = SingleDeviceSharding(topo.devices[0])
            place = lambda a: one                          # noqa: E731
            kw = captured["kw"]
        else:
            host = step._mesh
            chips = Mesh(np.array(topo.devices[:host.devices.size])
                         .reshape(host.devices.shape), host.axis_names)
            # what the program reads while it traces
            step._process_mesh._jax_mesh = chips

            def move(s):
                return NamedSharding(chips, s.spec) \
                    if isinstance(s, NamedSharding) else s

            place = lambda a: move(a.sharding) if isinstance(   # noqa: E731
                getattr(a, "sharding", None), NamedSharding) \
                else NamedSharding(chips, jax.sharding.PartitionSpec())
            kw = jax.tree_util.tree_map(
                move, captured["kw"],
                is_leaf=lambda x: isinstance(x, NamedSharding))
        args = aot.to_struct(captured["args"], place)
        return jax.jit(captured["fun"], **kw).lower(*args).compile()
