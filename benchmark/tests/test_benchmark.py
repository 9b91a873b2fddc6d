"""Checks of the benchmark itself, on the CPU, in under a minute:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

The manifest's names against the contract's character rules (PR 22 was
refused over a layer's name), every file a cell needs found by name, the
trace reduction on a small recorded v5e trace, the traffic generator's
determinism, and each traffic kind end to end at ``gpt_tiny`` through the
functions ``run.py`` calls. No described-topology call at import time.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from lib import flops, runner, stats, xplane  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LAYERS = {"train_step", "serving_engine", "kernels", "mesh", "device"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRACE = os.path.join(BENCH, "testdata", "small_v5e.xplane.pb")

TINY = dict(
    model_class="GPTForCausalLM", config_class="GPTConfig",
    reference="gpt", dtype="bfloat16", vocab_size=1024, hidden_size=128,
    num_layers=2, num_heads=4, intermediate_size=512,
    max_position_embeddings=256,
    model_kwargs={"dropout": 0.0, "attention_dropout": 0.0,
                  "recompute": False, "lm_ce_chunks": 8},
    trainer={"optimizer": "AdamW",
             "optimizer_kwargs": {"learning_rate": 1e-4,
                                  "weight_decay": 0.01, "factored_v": True,
                                  "moment_dtype": "bfloat16"},
             "grad_clip_norm": 1.0},
    engine={"max_slots": 4, "block_size": 16, "prefill_chunk": 32,
            "num_blocks": 64})
TINY_TRAIN = {"kind": "train_stream", "batch": 4, "seq": 128,
              "steps_per_dispatch": 2, "traced_dispatches": 3,
              "reference": {"tolerance": 0.02}}
TINY_CHAT = {
    "kind": "closed_loop", "clients": 4,
    "pairs": [[8, 12], [14, 30], [19, 9], [24, 16], [29, 22], [34, 5],
              [40, 18], [47, 48], [55, 11], [66, 26], [82, 14], [120, 20]],
    "ramp_s": 0.5, "traced_s": 0.3, "temperature": 0.0,
    "reference": {"requests": 2, "pad_to": 128, "margin": 0.15}}


@pytest.fixture(scope="module")
def manifest():
    return runner.load_manifest()


def _all_metrics(m):
    return m["end_to_end"] + m["per_layer"]


def test_every_name_unit_and_layer_meets_the_character_rules(manifest):
    names = [e["name"] for e in manifest["configs"] + manifest["workloads"]
             + _all_metrics(manifest)]
    names += [w[k] for w in manifest["workloads"]
              for k in ("config", "traffic")]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    names += [p["layer"] for p in manifest["per_layer"]]
    for n in names:
        assert NAME.match(n), n
    for e in _all_metrics(manifest):
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    assert {p["layer"] for p in manifest["per_layer"]} <= LAYERS
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [e["name"] for e in manifest[group]]
        assert len(ns) == len(set(ns))


def test_manifest_keeps_to_the_contract(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    for e in manifest["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    for p in manifest["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    assert any(e["name"] == "setup_s" and "workloads" not in e
               for e in manifest["end_to_end"])
    for e in manifest["configs"] + manifest["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in manifest["workloads"])
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_every_cell_finds_its_files_and_reports_what_it_must(manifest):
    e2e = {e["name"]: e for e in manifest["end_to_end"]}
    used = set()
    for w in manifest["workloads"]:
        cfg_entry = runner.by_name(manifest["configs"], w["config"], "c")
        used.add(w["config"])
        assert cfg_entry["file"].startswith("benchmark/")
        cfg = runner.load_json(ROOT, cfg_entry["file"])
        traffic = runner.load_json(BENCH, "traffic", w["traffic"] + ".json")
        for folder, name in (("kinds", traffic["kind"]),
                             ("references", cfg["reference"])):
            assert os.path.isfile(os.path.join(BENCH, folder, name + ".py"))
        assert hasattr(runner.load_module("kinds", traffic["kind"]), "run")
        mine = [e["name"] for e in
                runner.metrics_of(manifest, "end_to_end", w["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        layer = runner.metrics_of(manifest, "per_layer", w["name"])
        assert layer
        for p in layer:
            assert os.path.isfile(os.path.join(
                BENCH, "layer_metrics", p["name"] + ".py")), p["name"]
            assert p["moves"] in e2e and p["moves"] in mine, p["name"]
    assert used == {c["name"] for c in manifest["configs"]}


def test_configurations_keep_the_published_widths(manifest):
    # Brown et al. 2020, table 2.1: d_model, n_heads, d_head, n_layers as
    # printed. The XL row's 24 heads of 128 do not make its d_model: a
    # size that departs from the print must be declared under "assumed"
    printed = {"gpt3-1p3b": (2048, 24, 128, 24),
               "gpt3-6p7b-l12": (4096, 32, 128, 32)}
    for c in manifest["configs"]:
        cfg = runner.load_json(ROOT, c["file"])
        hidden, heads, head_dim, depth = printed[c["name"]]
        assert (cfg["hidden_size"], cfg["head_dim"]) == (hidden, head_dim)
        assert cfg["num_heads"] * cfg["head_dim"] == cfg["hidden_size"]
        assert (cfg["num_heads"] != heads) == ("num_heads" in cfg["assumed"])
        assert cfg["intermediate_size"] == 4 * hidden
        assert cfg["vocab_size"] == 50304
        assert cfg["max_position_embeddings"] == 2048
        assert cfg["reduced"] == c["reduced"]
        assert (cfg["num_layers"] != depth) == ("num_layers" in c["reduced"])
        for k in ("source", "stands_for", "assumed"):
            assert cfg[k]


def test_trace_reduction_on_a_recorded_v5e_trace():
    tr = xplane.reduce_trace(TRACE)
    assert list(tr["chips"]) == ["/device:TPU:0"]
    chip = tr["chips"]["/device:TPU:0"]
    assert chip["programs"] == 5
    assert chip["window_s"] == pytest.approx(8.238287e-3, rel=1e-6)
    assert chip["busy_s"] == pytest.approx(4.706961e-3, rel=1e-6)
    assert xplane.worst_idle_share(tr) == pytest.approx(42.8648, abs=1e-3)
    top = tr["breakdown"]["device_ops"]
    assert top[0][0] == "ragged_paged_attention bf16[304,16,1,128]"
    assert top[0][1] == pytest.approx(3.155475e-3, rel=1e-6)
    assert len(top) == 10 and top == sorted(top, key=lambda kv: -kv[1])
    gaps = tr["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "python3: $api.py:3097 block_until_ready"
    assert sum(v for _, v in chip["gaps"].items()) == pytest.approx(
        chip["window_s"] - chip["busy_s"], rel=1e-6)
    kinds = {xplane.classify_kernel(op)[0]: op for op in chip["ops"]
             if xplane.classify_kernel(op)}
    assert set(kinds) == {"flash_fwd", "flash_bwd", "ragged_attn"}
    assert kinds["flash_fwd"]["count"] == 2


def test_flash_roofline_reader_on_the_recorded_trace():
    from lib.peaks import peaks_for

    class Cell:
        peaks = peaks_for("TPU v5 lite")
        log = staticmethod(lambda msg: None)

    read = runner.load_module("layer_metrics",
                              "kernels.flash_roofline.train").read
    share = read({"trace": xplane.reduce_trace(TRACE)}, Cell)
    # 2 forward + 2 backward calls of 16 heads x 2048 x 128: 7 matmuls of
    # 2*2048*2048*128 FLOP a head, halved by the mask, at 197 TFLOP/s
    least = 2 * 7 * 2 * 2048 * 2048 * 128 * 16 / 2 / 197e12
    assert share == pytest.approx(
        100 * least / (0.000414596 + 0.000725842), rel=1e-6)
    assert 30 < share < 60
    assert read({"trace": None}, Cell) is None


def test_parse_hlo_and_collectives():
    op = xplane.parse_hlo(
        "%all-reduce-start.3 = (bf16[4,2048,4096]{2,1,0}, bf16[4,2048,4096]"
        "{2,1,0}) all-reduce-start(bf16[4,2048,4096]{2,1,0} %fusion.1), "
        "channel_id=7, to_apply=%add")
    assert op["base"] == "all-reduce-start"
    assert op["opcode"] == "all-reduce-start" and xplane.is_collective(op)
    assert op["results"][0] == ("bf16", (4, 2048, 4096))
    assert op["operands"] == [("bf16", (4, 2048, 4096))]
    op = xplane.parse_hlo("%fusion.9 = f32[]{:T(256)} fusion(f32[8]{0} %x), "
                          "kind=kLoop, calls=%all-reduce_like")
    assert not xplane.is_collective(op) and op["opcode"] == "fusion"
    assert xplane.op_label(op) == "fusion f32[]"
    assert xplane.classify_kernel(op) is None


def test_chat_traffic_is_one_multiset_in_a_seeded_order(manifest):
    import math
    from statistics import NormalDist

    import numpy as np

    closed = runner.load_module("kinds", "closed_loop")
    tf = runner.load_json(BENCH, "traffic", "chat_closed48.json")
    pairs = [tuple(p) for p in tf["pairs"]]

    # the file's pairs are what its "pairs_from" says
    def quantiles(median, sigma, lo, hi):
        return [int(min(hi, max(lo, round(median * math.exp(
            sigma * NormalDist().inv_cdf((i + 0.5) / 64))))))
            for i in range(64)]

    prompts, outputs = quantiles(256, 0.8, 32, 1536), \
        quantiles(128, 0.6, 16, 384)
    order = np.random.default_rng(0).permutation(64)
    assert pairs == [(prompts[i], outputs[int(j)])
                     for i, j in enumerate(order)]
    assert all(p + o <= 2048 for p, o in pairs)
    # the pool the configuration reserves is what the clients' requests
    # fill: at their final sizes, a little more than its pages
    eng = runner.load_json(BENCH, "configs", "gpt3-1p3b.json")["engine"]
    assert eng["max_slots"] == tf["clients"]
    pages = sum(math.ceil((p + o) / eng["block_size"]) for p, o in pairs) \
        / len(pairs) * tf["clients"]
    assert 0.75 < pages / eng["num_blocks"] < 1.0
    # the longest requests the reference check may take span chunks, pages
    fit = sorted(p + o for p, o in pairs
                 if p + o <= tf["reference"]["pad_to"])[-2:]
    assert min(fit) > 3 * eng["prefill_chunk"]
    a, b = closed.make_plan(tf, 2 ** 31 + 5), closed.make_plan(tf, 7)
    assert a == closed.make_plan(tf, 2 ** 31 + 5) and a != b
    assert sorted(a) == sorted(b) == sorted(pairs)
    t = closed.prompt_tokens(2 ** 31 + 5, 3, 50304, 64)
    assert t == closed.prompt_tokens(2 ** 31 + 5, 3, 50304, 64)
    assert t != closed.prompt_tokens(2 ** 31 + 5, 4, 50304, 64)
    assert all(0 <= x < 50304 for x in t)


def test_arithmetic_of_flops_and_percentiles():
    f = flops.train_flops_per_token(1_315_819_520, 24, 2048, 2048)
    assert f["6N"] == 6 * 1_315_819_520
    assert f["6N_plus_attention"] - f["6N"] == 6 * 24 * 2048 * 2048
    shp = ("bf16", (16, 2048, 128))
    c = flops.flash_call_cost("flash_fwd", 16, 2048, 128, [shp] * 3,
                              [shp, ("f32", (16, 2048, 1))])
    assert c["flops"] == 2 * 2 * 2048 * 2048 * 128 * 16 / 2
    assert c["bytes"] == 4 * 16 * 2048 * 128 * 2 + 16 * 2048 * 4
    assert flops.least_seconds(c, {"bf16_flops": 197e12,
                                   "hbm_bytes_per_s": 819e9}
                               )["bound"] == "compute"
    assert stats.median([3, 1, 2]) == 2
    assert stats.percentile([1, 2, 3, 4, 5], 95) == pytest.approx(4.8)
    assert stats.median([1, 2, float("inf")]) == 2
    assert stats.median([1, float("inf"), float("inf")]) == float("inf")


def _tiny_cell(traffic, trace, seconds):
    import jax

    import paddle_tpu as pt
    from lib.compiles import CompileCounter

    return runner.Cell(pt, TINY, traffic, 2 ** 31 + 52, seconds, trace, 1,
                       jax.devices()[:1], None, CompileCounter(),
                       time.perf_counter())


def test_train_stream_end_to_end_at_gpt_tiny(manifest):
    cell = _tiny_cell(TINY_TRAIN, 0, 1.0)
    rec = runner.run_cell(cell)
    assert rec["problems"] == [] and rec["compiles_in_window"] == 0
    line = runner.result_line(cell, rec, manifest, "train_1p3b",
                              {"platform": "cpu", "kind": "cpu", "count": 1})
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == 2 * len(rec["host"]["step_ms"])
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["unit"] == "s"
    json.dumps(line)


def test_closed_loop_end_to_end_at_gpt_tiny_traced(manifest):
    cell = _tiny_cell(TINY_CHAT, 1, 1.5)
    rec = runner.run_cell(cell)
    # the CPU has no device plane: that alone may make the run incorrect
    assert rec["problems"] == [] and rec["attempted"] > 0
    assert set(rec["end_to_end"]) == {"serve_tokens_per_s", "itl_ms_p95",
                                      "setup_s"}
    line = runner.result_line(cell, rec, manifest, "serve_chat_1p3b",
                              {"platform": "cpu", "kind": "cpu", "count": 1})
    assert not line["correct"] and "breakdown" not in line
    assert rec["problems"] == ["no device operation in the trace"]
    assert set(line["metrics"]) == {"serving_engine.step_ms_p50",
                                    "serving_engine.tokens_per_step",
                                    "serving_engine.ttft_ms_p50",
                                    "serving_engine.submit_ms_p50",
                                    "serving_engine.kv_pool_occupancy"}
    assert 0 < line["metrics"]["serving_engine.kv_pool_occupancy"][
        "value"] <= 100
    assert 1 <= line["metrics"]["serving_engine.tokens_per_step"]["value"] \
        <= 36
    from paddle_tpu import observability
    assert not observability.enabled()


def test_the_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "train_1p3b", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
