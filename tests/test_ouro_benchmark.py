"""What this repo's benchmark gained with Ouro-2.6B, on the CPU: the two
new cells' traffic files, the closed-loop kind over a tiny Ouro
configuration, and the two roofline readers on the benchmark's recorded
v5e trace."""
import json
import os
import statistics
import sys
import time

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")
sys.path[:0] = [_BENCH, _ROOT]

from lib import runner, serve_bytes, xplane  # noqa: E402
from lib.peaks import peaks_for  # noqa: E402

TINY_OURO = dict(
    model_class="OuroForCausalLM", config_class="OuroConfig",
    reference="ouro", dtype="float32", vocab_size=512, hidden_size=64,
    num_layers=3, num_heads=4, intermediate_size=128,
    max_position_embeddings=256, tie_word_embeddings=False,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    rms_norm_eps=1e-6, rope_theta=1000000, total_ut_steps=3,
    early_exit_threshold=1,
    model_kwargs={"num_kv_heads": 4, "rms_norm_eps": 1e-6,
                  "rope_base": 1000000.0, "total_ut_steps": 3,
                  "early_exit_threshold": 1.0},
    engine={"max_slots": 3, "block_size": 16, "prefill_chunk": 32,
            "num_blocks": 32, "max_seq_len": 128})
TINY_TRAFFIC = {
    "kind": "closed_loop", "clients": 3,
    "pairs": [[8, 12], [14, 30], [19, 9], [24, 16], [29, 22], [34, 5]],
    "ramp_s": 0.5, "traced_s": 0.3, "temperature": 0.0,
    "reference": {"requests": 2, "pad_to": 128, "margin": 1e-3}}
NEW_CELLS = ("serve_reason_ouro2p6b", "serve_longprompt_1p3b")


@pytest.fixture(scope="module")
def manifest():
    return runner.load_manifest()


def _quantiles(n, median, sigma, lo, hi):
    nd = statistics.NormalDist()
    return [int(min(hi, max(lo, round(median * np.exp(
        sigma * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]


@pytest.mark.parametrize("cell,prompts,outputs", [
    (NEW_CELLS[0], _quantiles(12, 96, 0.5, 48, 224),
     _quantiles(12, 288, 0.3, 192, 448)),
    (NEW_CELLS[1], [1024, 1136, 1248, 1360, 1456, 1568, 1680, 1792],
     [16, 24, 32, 40, 40, 48, 56, 64])])
def test_new_traffic_is_one_multiset_in_a_seeded_order(manifest, cell,
                                                       prompts, outputs):
    wl, cfg, tf = runner.cell_files(manifest, cell)
    perm = np.random.default_rng(0).permutation(len(prompts))
    assert tf["pairs"] == [[p, outputs[int(j)]]
                           for p, j in zip(prompts, perm)]
    kind = runner.load_module("kinds", tf["kind"])
    plans = [kind.make_plan(tf, seed) for seed in (1, 2 ** 31 + 7)]
    assert plans[0] != plans[1]
    assert all(sorted(p) == sorted(map(tuple, tf["pairs"])) for p in plans)
    eng = cfg["engine"]
    longest = max(p + o for p, o in tf["pairs"])
    assert longest <= eng.get("max_seq_len",
                              cfg["max_position_embeddings"])
    # the reference check compares the two longest: both fit its padding,
    # and are three pages or more
    two = sorted(p + o for p, o in tf["pairs"])[-2:]
    assert two[1] <= tf["reference"]["pad_to"]
    assert two[0] >= 3 * eng["block_size"]
    assert tf["clients"] <= eng["max_slots"]
    # at most one page short of the pool with every client on the longest
    worst = tf["clients"] * -(-(longest + 1) // eng["block_size"])
    assert worst <= eng["num_blocks"] + eng["block_size"]


def test_new_cells_report_what_the_serving_cells_report(manifest):
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" not in m or "serve_chat_1p3b" not in m["workloads"]:
            continue
        assert m["workloads"][0] == "serve_chat_1p3b"
        assert set(NEW_CELLS) <= set(m["workloads"]), m["name"]
    new = [m for m in manifest["per_layer"]
           if "serve_chat_1p3b" not in m.get("workloads", ())
           and NEW_CELLS[0] in m.get("workloads", ())]
    # the kernel's share of its bound is read in the long-prompt cell too
    assert [(m["name"], m["workloads"]) for m in new] == [
        ("kernels.ragged_attn_roofline.serve", list(NEW_CELLS)),
        ("serving_engine.step_hbm_roofline.serve", list(NEW_CELLS[:1]))]
    assert all(m["moves"] == "itl_ms_p95" and m["unit"] == "%"
               for m in new)


def test_closed_loop_over_a_tiny_ouro_traced(manifest):
    import jax

    import paddle_tpu as pt
    from lib.compiles import CompileCounter

    cell = runner.Cell(pt, TINY_OURO, TINY_TRAFFIC, 2 ** 31 + 52, 1.5, 1,
                       1, jax.devices()[:1], None, CompileCounter(),
                       time.perf_counter())
    rec = runner.run_cell(cell)
    assert rec["problems"] == [] and rec["attempted"] > 0
    steps = [s for s in rec["spans"] if s["name"] == "serving.ragged_step"]
    assert steps and all(
        (s["args"]["passes"], s["args"]["cache_layers"]) == (3, 9)
        for s in steps)
    line = runner.result_line(cell, rec, manifest, NEW_CELLS[0],
                              {"platform": "cpu", "kind": "cpu", "count": 1})
    # no device plane on the CPU: the two rooflines find nothing to read
    # and are left out, the spans' metrics are there
    assert rec["problems"] == ["no device operation in the trace"]
    assert {"serving_engine.step_ms_p50", "serving_engine.tokens_per_step",
            "serving_engine.host_ms_per_step",
            "serving_engine.pool_pages_in_use"} <= set(line["metrics"])
    assert not [k for k in line["metrics"] if "roofline" in k]
    json.dumps(line)
    assert not pt.observability.enabled()


class _Cell:
    peaks = peaks_for("TPU v5 lite")
    seconds = 40.0
    traffic = {"traced_s": 2.0}
    config = {"dtype": "bfloat16", "hidden_size": 2048, "vocab_size": 49152,
              "num_heads": 16, "model_kwargs": {"num_kv_heads": 16},
              "engine": {"block_size": 128}}
    log = staticmethod(lambda msg: None)


def _record(with_attrs=True):
    """The benchmark's recorded trace (one ragged kernel instruction over
    pools bf16[16,240,128,128]) and synthetic spans: a step every 100 ms,
    3 ms of enqueue and 80 ms of device wait, 10 live pages."""
    args = {"rows": 6, "tokens": 6, "impl": "pallas", "live_pages": 10}
    if with_attrs:
        args.update(passes=4, cache_layers=192, weight_bytes=4_933_287_936)
    spans = []
    for i in range(400):
        ts = 1e6 * (1000.0 + 0.1 * i)
        spans.append({"name": "serving.ragged_step", "ts": ts,
                      "dur": 3000.0, "args": dict(args)})
        spans.append({"name": "serving.device_wait", "ts": ts + 3100.0,
                      "dur": 80000.0, "args": {}})
    return {"trace": xplane.reduce_trace(os.path.join(
        _BENCH, "testdata", "small_v5e.xplane.pb")), "spans": spans}


def test_roofline_readers_on_the_recorded_trace():
    rec = _record()
    ops = [op for op in rec["trace"]["chips"]["/device:TPU:0"]["ops"]
           if (xplane.classify_kernel(op) or ("",))[0] == "ragged_attn"]
    calls = sum(op["count"] for op in ops)
    spent = sum(op["seconds"] for op in ops)
    page = 2 * 16 * 128 * 128 * 2
    assert serve_bytes.kv_page_bytes(("bf16", (16, 240, 128, 128))) == page
    assert serve_bytes.config_page_bytes(_Cell.config) == page
    kernel = runner.load_module(
        "layer_metrics", "kernels.ragged_attn_roofline.serve").read
    assert kernel(rec, _Cell) == pytest.approx(
        100 * calls * 10 * page / 819e9 / spent, rel=1e-9)
    step = runner.load_module(
        "layer_metrics", "serving_engine.step_hbm_roofline.serve").read
    least = 4 * 4_933_287_936 + 10 * 192 * page + 2048 * 49152 * 2
    assert serve_bytes.step_least_bytes(
        rec["spans"][0]["args"], page, _Cell.config) == least
    assert step(rec, _Cell) == pytest.approx(
        100 * least / 819e9 / 0.083, rel=1e-9)
    # a program whose span lacks the looped step's attributes gives
    # nothing to read; without a trace the kernel's share has nothing,
    # the step's needs the spans alone
    bare = _record(with_attrs=False)
    assert kernel(bare, _Cell) is None and step(bare, _Cell) is None
    spans_only = dict(rec, trace=None)
    assert kernel(spans_only, _Cell) is None
    assert step(spans_only, _Cell) == step(rec, _Cell)


def test_traced_steps_are_those_of_the_profiled_part():
    rec = _record()
    steps = serve_bytes.traced_steps(rec, _Cell)
    # 2 s from the window's middle, a step every 100 ms
    assert len(steps) == 20
    assert steps[0]["ts"] == pytest.approx(1e6 * 1020.0)


@pytest.mark.parametrize("tokens, rows, pages, pages_a_row", [
    (304, 48, 240, 16),         # serve_chat_1p3b, serve_longprompt_1p3b
    (262, 6, 22, 8),            # serve_reason_ouro2p6b
])
def test_trace_reducer_takes_only_the_attention_call_for_attention(
        tokens, rows, pages, pages_a_row):
    """``xplane.classify_kernel`` tells a ``tpu_custom_call`` by its
    operands. The in-place KV write takes both pools too: counted as
    attention it would double ``kernels.ragged_attn_roofline.serve``'s
    calls. None of its ``s32`` operands (the grid's bound, then the visit
    tables) has rank 2, which is what keeps it out."""
    import importlib

    import jax
    import jax.numpy as jnp

    paged = importlib.import_module(
        "paddle_tpu.incubate.nn.pallas.paged_attention")
    hlo_type = {"int32": "s32", "bfloat16": "bf16", "float32": "f32"}

    def calls(fn, *args):
        """The Pallas calls in ``fn``'s jaxpr, as the reducer sees a
        custom call: operand and result (dtype, dims) pairs."""
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append({"target": "tpu_custom_call", **{
                        key: [(hlo_type[str(v.aval.dtype)],
                               tuple(v.aval.shape)) for v in vs]
                        for key, vs in (("operands", eqn.invars),
                                        ("results", eqn.outvars))}})
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return found

    s = jax.ShapeDtypeStruct
    pool = s((16, pages, 128, 128), jnp.bfloat16)
    new = s((tokens, 1, 16, 128), jnp.bfloat16)
    write, = calls(
        lambda *a: paged.paged_kv_write_chunk(*a, use_kernel=True,
                                              interpret=False),
        pool, pool, new, new, s((tokens, pages_a_row), jnp.int32),
        s((tokens, 1), jnp.int32))
    row = s((rows,), jnp.int32)
    attn, = calls(
        lambda q, k, v, bt, cl, ql, qs: paged.ragged_paged_attention(
            q, k, v, bt, cl, ql, q_starts=qs, use_kernel=True,
            interpret=False),
        s((tokens, 16, 128), jnp.bfloat16), pool, pool,
        s((rows, pages_a_row), jnp.int32), row, row, row)
    assert write["operands"][:2] == [("s32", ()), ("s32", (tokens,))]
    assert all(len(o[1]) < 2 for o in write["operands"] if o[0] == "s32")
    assert [o for o in write["operands"] if len(o[1]) == 4] \
        == write["results"] == [("bf16", pool.shape)] * 2
    assert xplane.classify_kernel(write) == ("other_pallas", {})
    assert xplane.classify_kernel(attn) == ("ragged_attn",
                                            {"pool": pool.shape})
