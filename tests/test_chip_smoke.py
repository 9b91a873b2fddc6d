"""chip_smoke.py on the CPU: the script refuses to run, its phase bodies
pass at ``gpt_tiny``, importing the package claims no backend, and the
compile cache lands where it was told to."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402


def _start(code_or_path, env_extra=None, cwd=None, script=False):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = _ROOT
    env.update(env_extra or {})
    cmd = [sys.executable, code_or_path] if script \
        else [sys.executable, "-c", code_or_path]
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env,
                            cwd=cwd or _ROOT)


def _run(*a, **kw):
    p = _start(*a, **kw)
    p.stdout_text, p.stderr_text = p.communicate(timeout=300)
    return p


def test_script_refuses_cpu_and_names_platform():
    out = _run(os.path.join(_ROOT, "chip_smoke.py"), script=True)
    assert out.returncode != 0
    assert "platform='cpu'" in out.stderr_text, out.stderr_text
    # no result line: nothing on stdout parses as the ok object
    assert '"ok"' not in out.stdout_text


def test_imports_create_no_backend():
    """``import paddle_tpu``, the launcher and ``bench`` must not
    initialise a JAX backend: a parent that did would hold the chip and
    its child could not get it."""
    out = _run(
        "import paddle_tpu, paddle_tpu.distributed.launch.main, bench\n"
        "from jax._src import xla_bridge\n"
        "print('BACKENDS', sorted(xla_bridge._backends))\n")
    assert out.returncode == 0, out.stderr_text
    assert "BACKENDS []" in out.stdout_text, out.stdout_text


# the module is loaded by path: it needs jax only, and the probe then
# costs one jax import instead of the whole package
_CACHE_PROBE = (
    "import importlib.util, jax\n"
    "spec = importlib.util.spec_from_file_location('cc', %r)\n"
    "cc = importlib.util.module_from_spec(spec)\n"
    "spec.loader.exec_module(cc)\n"
    "d = cc.place_compile_cache()\n"
    "print('CACHE', d, '|', jax.config.jax_compilation_cache_dir)\n"
    % os.path.join(_ROOT, "paddle_tpu", "config", "compile_cache.py"))


def test_cache_dir_is_env_var_when_set(tmp_path):
    where = str(tmp_path / "placed")
    out = _run(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": where})
    assert out.returncode == 0, out.stderr_text
    # returned AND what jax itself will use: nothing else was set in code
    assert "CACHE %s | %s" % (where, where) in out.stdout_text, out.stdout_text


def test_cache_dir_is_checkout_when_unset(tmp_path):
    want = os.path.join(_ROOT, ".jax_cache")
    # two processes, started from different directories
    procs = [_start(_CACHE_PROBE, cwd=cwd) for cwd in (_ROOT, str(tmp_path))]
    for p in procs:
        stdout, stderr = p.communicate(timeout=300)
        assert p.returncode == 0, stderr
        assert "CACHE %s | %s" % (want, want) in stdout, stdout


# ------------------------------------------------- phase bodies, gpt_tiny
_TINY_KERNELS = dict(
    flash=((1, 128),), heads=2, head_dim=64,
    page=8, pages=8, pages_per_seq=2, rows=4, tokens=12,
    norm_rows=16, hidden=128,
    moe=dict(tokens=64, hidden=32, dff=32, experts=4, topk=2),
    cell=dict(rows=6, pages=40, pages_per_seq=8, tokens=24, chunk=12),
)


def test_kernels_phase_tiny():
    rep = chip_smoke.kernels_phase(_TINY_KERNELS, dtype="float32", tol=1e-4)
    assert {"flash_fwd_s128", "flash_bwd_s128_dq", "ragged_fp",
            "ragged_int8", "ragged_cell_fp", "ragged_cell_int8",
            "ragged_cell_fp_max_abs", "paged_decode", "moe_ffn_sorted", "rms_norm",
            "layer_norm"} <= set(rep)


def test_train_phase_tiny():
    import paddle_tpu as pt

    cfg = pt.models.gpt_tiny(dropout=0.0, attention_dropout=0.0,
                             lm_ce_chunks=8)
    rep = chip_smoke.train_phase(cfg, batch=2, seq=64, single_steps=2,
                                 chained=2, dtype="float32")
    assert rep["losses"][-1] < rep["losses"][0]
    # off-TPU attention is the XLA composition, and the report says so
    assert rep["attention_impl"] == "xla"
    assert rep["flash_fwd_kernels"] == 0


def test_serve_phase_tiny():
    import paddle_tpu as pt

    cfg = pt.models.gpt_tiny(dropout=0.0, attention_dropout=0.0)
    model = chip_smoke.build_serve_model(cfg, dtype="float32")
    prompts = chip_smoke.make_prompts(cfg.vocab_size, (5, 11, 5))
    refs = chip_smoke.serve_references(model, prompts, 4)
    for bs in (8, 16):
        rep = chip_smoke.serve_phase(
            model, prompts, refs, block_size=bs, max_slots=2,
            prefill_chunk=8, pool_tokens=256, max_new_tokens=4,
            stream_timeout_s=120.0)
        assert rep["attention_impl"] == "xla"
        # on the CPU the scatter writes KV and the steps donate nothing
        assert rep["kv_write"] == "xla" and not rep["pools_donated"]
        assert rep["ragged_compiles"] == 1 and rep["pool_drained"]
        assert rep["tokens"] == 4 * len(prompts)


def test_ouro_serve_phase_tiny():
    """The looped step's phase at ``ouro_tiny``: streams on the float32
    reference's argmax, and a margin the streams miss is reported."""
    import paddle_tpu as pt

    cfg = pt.models.ouro_tiny(**chip_smoke.OURO_INIT)
    size = dict(block_size=16, max_slots=2, prefill_chunk=8,
                pool_tokens=256, max_seq_len=64, prompt_lens=(5, 19, 5),
                max_new_tokens=4, stream_timeout_s=120.0)
    rep = chip_smoke.ouro_serve_phase(cfg, size, margin=1e-3,
                                      dtype="float32")
    assert rep["ragged_compiles"] == 1 and rep["pool_drained"]
    assert rep["tokens"] == 12 and rep["reference_shortfall"] < 1e-3
    assert (rep["kv_pools"], rep["passes"], rep["pool_pages"]) == (9, 3, 16)
    with pytest.raises(AssertionError, match="under the float32"):
        chip_smoke.ouro_serve_phase(cfg, size, margin=-1.0,
                                    dtype="float32")


def test_xing4_serve_phase_tiny():
    """The latent step's phase at ``xing4_tiny``: one latent pool a
    layer, streams on the float32 reference's argmax."""
    import paddle_tpu as pt

    size = dict(block_size=16, max_slots=2, prefill_chunk=8,
                pool_tokens=256, max_seq_len=64, prompt_lens=(5, 19, 5),
                max_new_tokens=4, stream_timeout_s=120.0)
    rep = chip_smoke.xing4_serve_phase(pt.models.xing4_tiny(), size,
                                       margin=1e-3, dtype="float32")
    assert rep["ragged_compiles"] == 1 and rep["pool_drained"]
    assert rep["tokens"] == 12 and rep["reference_shortfall"] < 1e-3
    assert (rep["kv_pools"], rep["experts"], rep["hc_streams"]) == (3, 8, 4)
    assert (rep["attention_impl"], rep["kv_write"]) == ("xla", "xla")
    # the chip's phase is the serving cell's cut of the published model
    cfg = pt.models.xing4_29B_A4B(**chip_smoke.XING4_CUT)
    assert (cfg.num_layers, cfg.first_k_dense_replace, cfg.hidden_size,
            cfg.n_routed_experts, cfg.latent_dim) == (6, 1, 3584, 64, 576)


def test_serve_phase_reports_wrong_token():
    import paddle_tpu as pt

    cfg = pt.models.gpt_tiny(dropout=0.0, attention_dropout=0.0)
    model = chip_smoke.build_serve_model(cfg, dtype="float32")
    prompts = chip_smoke.make_prompts(cfg.vocab_size, (5,))
    refs = chip_smoke.serve_references(model, prompts, 3)
    refs[0][-1] = (refs[0][-1] + 1) % cfg.vocab_size
    with pytest.raises(AssertionError, match="stream != generate"):
        chip_smoke.serve_phase(
            model, prompts, refs, block_size=8, max_slots=2,
            prefill_chunk=8, pool_tokens=256, max_new_tokens=3,
            stream_timeout_s=120.0)


def test_multichip_train_phase_tiny():
    """The four-chip arm's body on four of the suite's host devices: the
    dp x mp mesh is real, and the shard checks hold."""
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    import chip_smoke_multichip

    import paddle_tpu as pt

    cfg = pt.models.gpt_tiny(dropout=0.0, attention_dropout=0.0,
                             lm_ce_chunks=8)
    rep = chip_smoke_multichip.multichip_train_phase(
        cfg, dp=2, mp=2, batch=4, seq=32, steps=2, dtype="float32")
    assert rep["devices"] == [0, 1, 2, 3]
    assert rep["qkv_weight_shard"] == [128, 192]     # 384 columns / mp
    assert rep["batch_shard"] == [2, 32]             # 4 rows / dp


def test_require_tpu_rejects_cpu():
    with pytest.raises(RuntimeError, match="platform='cpu'"):
        chip_smoke.require_tpu()


def test_unknown_device_kind_is_an_error():
    from paddle_tpu.device.peaks import chip_name, chip_peaks

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert chip_name(Dev) == "v5e"
    assert chip_peaks(Dev).bf16_flops == 197e12
    Dev.device_kind = "TPU v9 mega"
    with pytest.raises(ValueError, match="no published peaks"):
        chip_name(Dev)
