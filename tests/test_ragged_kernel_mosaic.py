"""The ragged paged-attention kernel and the in-place KV write compiled by
the TPU's own compiler for a described (not attached) v5e, at the serving
cells' shapes: what Mosaic refuses on the chip — a slice off the tiling,
too much VMEM — it refuses here, at no chip time. Nothing runs, so nothing
here says the results are right or fast (tests/test_paged_attention.py
covers the first in interpret mode; the chip covers both).

The topology is described inside a fixture, never at import: only one
process may load libtpu unless the runner allows more, and every xdist
worker imports every test file."""
import importlib
import os
import re

import pytest

import jax
import jax.numpy as jnp

paged = importlib.import_module(
    "paddle_tpu.incubate.nn.pallas.paged_attention")

_TPU_ENV = {"TPU_LOG_DIR": "disabled", "TPU_ACCELERATOR_TYPE": "v5litepod-4",
            "TPU_WORKER_HOSTNAMES": "localhost", "TPU_SKIP_MDS_QUERY": "1"}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    saved = {k: os.environ.get(k) for k in _TPU_ENV}
    os.environ.update({k: v for k, v in _TPU_ENV.items()
                       if saved[k] is None})
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


# tokens, heads, kv heads, head dim, pages, page, rows, pages a row, int8
SHAPES = {
    "serve_chat_1p3b": (304, 16, 16, 128, 240, 128, 48, 16, False),
    "serve_chat_1p3b_int8": (304, 16, 16, 128, 240, 128, 48, 16, True),
    "gqa_group4": (304, 32, 8, 128, 240, 128, 48, 16, False),
    # a token budget whose scratch does not fit beside all heads: the
    # heads are blocked by what fits
    "tokens_2048": (2048, 16, 16, 128, 240, 256, 8, 16, False),
    # a head dim of 64 is fetched by the block pipeline, a page a visit
    "head_dim_64": (64, 16, 16, 64, 64, 128, 4, 4, False),
    "head_dim_64_gqa_int8": (64, 32, 8, 64, 64, 128, 4, 4, True),
    # GQA 16:1 over 2 KV heads: one head a block, 8 pages of 64 KiB a
    # visit, a decode token's 16 query rows a q block
    "serve_reason_nemotron3nano_l18": (384, 32, 2, 128, 2400, 128, 128, 30,
                                       False),
    "serve_reason_nemotron3nano_l18_int8": (384, 32, 2, 128, 2400, 128,
                                            128, 30, True),
    "serve_reason_ouro2p6b": (262, 16, 16, 128, 22, 128, 6, 8, False),
    # GQA 16:1 over 8 KV heads: the full layer of the Command A+ cell
    "serve_mixed_commandaplus_l4": (544, 128, 8, 128, 2048, 128, 32, 244,
                                    False),
}
# the Command A+ cell's windowed layers: a ring of 37 pages a slot, the
# table a row's 244 logical pages, the window 4096 (and one that is no
# multiple of the page)
WINDOWED = {
    "serve_mixed_commandaplus_l4": (544, 128, 8, 128, 32 * 37, 128, 32, 244,
                                    4096),
    "window_off_the_page": (544, 128, 8, 128, 32 * 37, 128, 32, 244, 4000),
}


def _pallas_op(hlo):
    """The compiled module's one Pallas call, parsed as a trace's op name
    is (``benchmark/lib/xplane.py``): the compiled text names its
    operands, and their shapes are in the layout constraints, in order."""
    _, xplane = _benchmark_libs()
    calls = [ln.strip().removeprefix("ROOT ") for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    op = xplane.parse_hlo(calls[0])
    op["operands"] = [(dt, tuple(int(x) for x in dims.split(",") if x))
                      for dt, dims in xplane._SHAPE.findall(re.search(
                          r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=",
                          calls[0]).group(1))]
    return op


def _benchmark_libs():
    """``benchmark/lib``'s ``xing4_kernels`` and ``xplane``."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmark"))
    try:
        from lib import xing4_kernels, xplane
    finally:
        sys.path.pop(0)
    return xing4_kernels, xplane


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_ragged_kernel_compiles_for_v5e(one_chip, name):
    """The per-head kernel at each shape, with the operands by which
    ``benchmark/lib/xplane.classify_kernel`` knows it in a trace: the s32
    [rows, pages a row] block table first, the two pools the last rank-4
    inputs (read by DMA from HBM or by the block pipeline, each still one
    operand)."""
    t, nh, nkv, d, pages, page, rows, pps, quant = SHAPES[name]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = {"q8": s((nkv, pages, page, d), jnp.int8),
            "s": s((nkv, pages, page), jnp.float32)} if quant \
        else s((nkv, pages, page, d), jnp.bfloat16)
    row = s((rows,), jnp.int32)

    def f(q, k, v, bt, cl, ql, qs):
        return paged.ragged_paged_attention(
            q, k, v, bt, cl, ql, q_starts=qs, use_kernel=True,
            interpret=False)

    compiled = jax.jit(f).lower(
        s((t, nh, d), jnp.bfloat16), pool, pool,
        s((rows, pps), jnp.int32), row, row, row).compile()
    op = _pallas_op(compiled.as_text())
    assert op["operands"][0] == ("s32", (rows, pps))
    _, xplane = _benchmark_libs()
    assert xplane.classify_kernel(op) == ("ragged_attn", {
        "pool": (nkv, pages, page, d)})


@pytest.mark.parametrize("name", sorted(WINDOWED))
def test_windowed_kernel_compiles_for_v5e(one_chip, name):
    """The per-head kernel with a window, over a ring pool: the same
    operands, so ``classify_kernel`` knows it as it knows a full layer's
    call (the window's readers tell the two apart by phase)."""
    t, nh, nkv, d, pages, page, rows, pps, window = WINDOWED[name]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((nkv, pages, page, d), jnp.bfloat16)
    row = s((rows,), jnp.int32)

    def f(q, k, v, bt, cl, ql, qs):
        return paged.ragged_paged_attention(
            q, k, v, bt, cl, ql, q_starts=qs, use_kernel=True,
            interpret=False, window=window)

    compiled = jax.jit(f).lower(
        s((t, nh, d), jnp.bfloat16), pool, pool,
        s((rows, pps), jnp.int32), row, row, row).compile()
    op = _pallas_op(compiled.as_text())
    _, xplane = _benchmark_libs()
    assert xplane.classify_kernel(op) == ("ragged_attn", {
        "pool": (nkv, pages, page, d)})


# tokens, kv heads, head dim, pages, page, pages a row
WRITE_SHAPES = {
    # the Command A+ cell's ring pools and full pool
    "serve_mixed_commandaplus_l4_ring": (544, 8, 128, 32 * 37, 128, 244),
    "serve_mixed_commandaplus_l4": (544, 8, 128, 2048, 128, 244),
    "serve_chat_1p3b": (304, 16, 128, 240, 128, 16),
    "serve_reason_ouro2p6b": (262, 16, 128, 22, 128, 8),
    "gqa_group4": (304, 8, 128, 240, 128, 16),
    "head_dim_64": (64, 16, 64, 64, 128, 4),
}


def _struct(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _write_args(one_chip, name):
    t, nkv, d, pages, page, pps = WRITE_SHAPES[name]
    s = _struct(one_chip)
    pool = s((nkv, pages, page, d), jnp.bfloat16)
    new = s((t, 1, nkv, d), jnp.bfloat16)
    return pool, pool, new, new, s((t, pps), jnp.int32), s((t, 1), jnp.int32)


@pytest.mark.parametrize("name", sorted(WRITE_SHAPES))
def test_kv_write_kernel_compiles_for_v5e(one_chip, name):
    def f(kp, vp, k, v, bt, pos):
        return paged.paged_kv_write_chunk(kp, vp, k, v, bt, pos,
                                          use_kernel=True, interpret=False)

    compiled = jax.jit(f).lower(*_write_args(one_chip, name)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _pool_copies(hlo, pool):
    """``copy`` instructions whose result has a pool's shape, in the
    kernel's layout or flattened over pages as the scatter wants it."""
    nkv, pages, page, d = pool
    shapes = {"bf16[%d,%d,%d,%d]" % pool,
              "bf16[%d,%d,%d]" % (nkv, pages * page, d)}
    return [m.group(0) for m in re.finditer(
        r"= (bf16\[[0-9,]+\])\S* copy\(", hlo) if m.group(1) in shapes]


@pytest.mark.parametrize("name", ["serve_longdoc_xing4_l6",
                                  "serve_reason_sarvam105b_l6"])
def test_latent_kernel_keeps_the_operands_the_benchmark_reads(one_chip,
                                                              name):
    """``benchmark/lib/xing4_kernels.is_latent_attn`` tells the latent
    kernel in a trace by its operands: exactly one rank-4 non-s32 pool
    [1, pages, page, dp] beside a rank-2 s32 block table, no rank-4
    result. At each latent cell's shapes, from its configuration file,
    the compiled layer's one Pallas call still has them (the pool read
    by DMA from HBM is one operand, not two)."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg_name = next(w["config"] for w in bench["workloads"]
                    if w["name"] == name)
    with open(os.path.join(root, next(c["file"] for c in bench["configs"]
                                      if c["name"] == cfg_name))) as f:
        config = json.load(f)
    xk, _ = _benchmark_libs()
    t, nh, d, dv, pages, page, rows, pps = LATENT_SHAPES[name]
    assert (d, page) == (xk.latent_pool_dim(config) - 64,
                         int(config["engine"]["block_size"]))
    a = _latent_args(one_chip, name)

    def attend(q, pool, bt, cl, ql, qs):
        return paged.ragged_latent_attention(
            q, pool, bt, cl, ql, q_starts=qs, value_dim=dv, scale=0.1,
            use_kernel=True, interpret=False)

    op = _pallas_op(jax.jit(attend).lower(*(a[k] for k in (
        "q", "pool", "bt", "cl", "ql", "qs"))).compile().as_text())
    assert [s for s in op["operands"] if len(s[1]) == 4] == \
        [("bf16", (1, pages, page, paged.latent_pool_dim(d)))]
    assert ("s32", (rows, pps)) in op["operands"]
    assert xk.is_latent_attn(op, config)


@pytest.mark.parametrize("kernel, donate, copies", [
    (True, True, 0),        # in place: what the serving step runs
    (True, False, 2),       # XLA protects each parameter pool by a copy
    (False, True, 4),       # the scatter's two layout changes a pool
])
def test_layer_writes_its_pools_in_place_only_donated(one_chip, kernel,
                                                      donate, copies):
    """One GPT-3 1.3B layer's KV write and ragged attention at
    ``serve_chat_1p3b``'s shapes. Neither half is enough: the scatter
    changes the layout of a donated pool all the same, and the kernel on
    undonated pools costs a copy of each."""
    t, nkv, d, pages, page, pps = WRITE_SHAPES["serve_chat_1p3b"]
    rows = 48

    def layer(kp, vp, k, v, bt_tok, pos, q, bt, cl, ql, qs):
        kp, vp = paged.paged_kv_write_chunk(
            kp, vp, k, v, bt_tok, pos, use_kernel=kernel, interpret=False)
        out = paged.ragged_paged_attention(
            q, kp, vp, bt, cl, ql, q_starts=qs, use_kernel=True,
            interpret=False)
        return out, kp, vp

    s = _struct(one_chip)
    row = s((rows,), jnp.int32)
    compiled = jax.jit(layer, donate_argnums=(0, 1) if donate else ()) \
        .lower(*_write_args(one_chip, "serve_chat_1p3b"),
               s((t, nkv, d), jnp.bfloat16), s((rows, pps), jnp.int32),
               row, row, row).compile()
    assert len(_pool_copies(compiled.as_text(),
                            (nkv, pages, page, d))) == copies
    aliased = compiled.memory_analysis().alias_size_in_bytes
    assert aliased == (2 * nkv * pages * page * d * 2 if donate else 0)


def test_sampler_sorts_once_under_one_conditional(one_chip):
    """The serving step's sampler at ``serve_chat_1p3b``'s shape, 48 rows
    of 50,304 logits with per-row temperature and nucleus. The lane keeps
    its sort's values (gathering them back by index was 24.5 ms of the
    42.5 ms step) and runs only where a row has a temperature."""
    from paddle_tpu.models.generation import _sample

    rows, vocab = 48, 50304
    s = _struct(one_chip)
    row = s((rows,), jnp.float32)
    hlo = jax.jit(_sample).lower(
        s((rows, vocab), jnp.bfloat16), s((2,), jnp.uint32), row, row) \
        .compile().as_text()
    assert len(re.findall(r" conditional\(", hlo)) == 1
    logits = r"\[%d,%d\]\S*" % (rows, vocab)
    assert len(re.findall(r"= \(f32%s, s32%s\) sort\(" % (logits, logits),
                          hlo)) == len(re.findall(r" sort\(", hlo)) == 1
    # the one gather left picks a token id a row
    assert re.findall(r"= (\w+\[[0-9,]*\])\S* gather\(", hlo) \
        == ["s32[%d]" % rows]


# ------------------------------------------------------- latent pages
# tokens, heads, latent, values, pages, page, rows, pages a row
LATENT_SHAPES = {
    "serve_longdoc_xing4_l6": (536, 32, 576, 512, 2048, 128, 24, 128),
    # 64 heads: a q block of 16 tokens is 1,024 query rows of 640 lanes
    "serve_reason_sarvam105b_l6": (560, 64, 576, 512, 1408, 128, 48, 37),
    "one_lane_tile_of_rope": (48, 16, 192, 128, 64, 128, 4, 8),
}


def _latent_args(one_chip, name):
    t, nh, d, dv, pages, page, rows, pps = LATENT_SHAPES[name]
    s = _struct(one_chip)
    row = s((rows,), jnp.int32)
    return dict(
        pool=s((1, pages, page, paged.latent_pool_dim(d)), jnp.bfloat16),
        new=s((t, d), jnp.bfloat16), bt_tok=s((t, pps), jnp.int32),
        pos=s((t,), jnp.int32), q=s((t, nh, d), jnp.bfloat16),
        bt=s((rows, pps), jnp.int32), cl=row, ql=row, qs=row)


@pytest.mark.parametrize("name", sorted(LATENT_SHAPES))
def test_latent_kernels_compile_for_v5e(one_chip, name):
    """The latent ragged-attention kernel (one pool, every head on the
    shared page, a dynamic grid of visits) and the in-place latent write
    at the latent cells' shapes."""
    dv = LATENT_SHAPES[name][3]
    a = _latent_args(one_chip, name)

    def attend(q, pool, bt, cl, ql, qs):
        return paged.ragged_latent_attention(
            q, pool, bt, cl, ql, q_starts=qs, value_dim=dv, scale=0.1,
            use_kernel=True, interpret=False)

    def write(pool, new, bt_tok, pos):
        return paged.paged_latent_write_chunk(
            pool, new, bt_tok, pos, use_kernel=True, interpret=False)

    for f, keys in ((attend, ("q", "pool", "bt", "cl", "ql", "qs")),
                    (write, ("pool", "new", "bt_tok", "pos"))):
        hlo = jax.jit(f).lower(*(a[k] for k in keys)).compile().as_text()
        assert hlo.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("kernel, donate, copies", [
    (True, True, 0),        # in place: what the serving step runs
    (True, False, 1),       # XLA protects the parameter pool by a copy
    # with one shared "head" the flattened pool's slot axis is the tiled
    # one already: the scatter needs no layout change here (it did, twice
    # a pool, at 16 KV heads) and is in place too once donated
    (False, True, 0),
])
def test_latent_layer_writes_its_pool_in_place_only_donated(
        one_chip, kernel, donate, copies):
    """One Xing4.0 layer's latent write and ragged attention at
    ``serve_longdoc_xing4_l6``'s shapes: the pool of 2048 pages moves
    only where neither the scatter nor a missing donation makes XLA copy
    it."""
    t, nh, d, dv, pages, page, rows, pps = \
        LATENT_SHAPES["serve_longdoc_xing4_l6"]
    a = _latent_args(one_chip, "serve_longdoc_xing4_l6")

    def layer(pool, new, bt_tok, pos, q, bt, cl, ql, qs):
        pool = paged.paged_latent_write_chunk(
            pool, new, bt_tok, pos, use_kernel=kernel, interpret=False)
        out = paged.ragged_latent_attention(
            q, pool, bt, cl, ql, q_starts=qs, value_dim=dv, scale=0.1,
            use_kernel=True, interpret=False)
        return out, pool

    compiled = jax.jit(layer, donate_argnums=(0,) if donate else ()) \
        .lower(*(a[k] for k in ("pool", "new", "bt_tok", "pos", "q", "bt",
                                "cl", "ql", "qs"))).compile()
    dp = paged.latent_pool_dim(d)
    assert len(_pool_copies(compiled.as_text(),
                            (1, pages, page, dp))) == copies
    aliased = compiled.memory_analysis().alias_size_in_bytes
    assert aliased == (pages * page * dp * 2 if donate else 0)


# rows, in, held experts, out (bfloat16): the two grouped matmuls of an
# expert layer of serve_reason_sarvam105b_l6 (dispatch_rows(560, 8, 32))
# and of serve_longdoc_xing4_l6 (dispatch_rows(536, 4, 64))
GMM_SHAPES = {
    "sarvam_gate_up": (8576, 4096, 32, 4096),
    "sarvam_down": (8576, 2048, 32, 4096),
    "xing4_gate_up": (10368, 3584, 64, 2048),
    "xing4_down": (10368, 1024, 64, 3584),
    # serve_reason_nemotron3nano_l18 (dispatch_rows(384, 6, 16)): relu²,
    # an up stack of 1856 columns, which no MXU-aligned width divides,
    # stored out x in (w_nk)
    "nemotron_up": (4352, 2688, 16, 1856),
    "nemotron_down": (4352, 1856, 16, 2688),
}
W_NK = {"nemotron_up"}


@pytest.mark.parametrize("name", sorted(GMM_SHAPES))
def test_grouped_matmul_with_live_blocks_compiles_for_v5e(one_chip, name):
    """The kernel that skips its dead row blocks: a second scalar-prefetch
    operand, index maps that read it, and the body under ``pl.when``."""
    from paddle_tpu.incubate.nn.pallas.moe_dispatch import grouped_matmul

    p, kdim, e, n = GMM_SHAPES[name]
    w_nk = name in W_NK

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda xp, w, gid, live: grouped_matmul(
        xp, w, gid, live, impl="pallas", interpret=False, w_nk=w_nk)).lower(
            s((p, kdim), jnp.bfloat16),
            s((e, n, kdim) if w_nk else (e, kdim, n), jnp.bfloat16),
            s((p // 128,), jnp.int32), s((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    # the weight stack is read as it lies: no copy of it a call (an [16,
    # 2688, 1856] one lies with 2688 minor, and would be copied)
    assert not re.search(r"= bf16\[%d,\d+,\d+\]\S* copy\(" % e, hlo)


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_ssd_scan_compiles_for_v5e_at_the_published_shapes(one_chip,
                                                           state_dtype):
    """The ragged chunked scan of ``serve_reason_nemotron3nano_l18``'s
    step: 64 heads of 64, a state of 128, 8 groups, a 384-token budget,
    128 rows. One Pallas call; with the state donated, written in place
    (aliased, no copy of it). Its operands are none of the existing
    readers' kernels: no rank-3 operand that leads with the 16 held
    experts (``is_moe_gmm``) and not the ragged attention kernel's
    (``classify_kernel``)."""
    import json

    from paddle_tpu.incubate.nn.pallas import ssd_scan

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron3-nano-l18-ep8.json")) as f:
        config = json.load(f)
    xk, xplane = _benchmark_libs()
    T, R = config["engine"]["token_budget"], config["engine"]["max_slots"]
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (s((H, P, T), jnp.bfloat16), s((H, T), jnp.float32),
            s((H,), jnp.float32), s((G, T, N), jnp.bfloat16),
            s((G, T, N), jnp.bfloat16), s((H,), jnp.float32),
            s((R, H, P, N), jnp.dtype(state_dtype)), s((R,), jnp.int32),
            s((R,), jnp.int32), s((R,), jnp.bool_))
    compiled = jax.jit(
        lambda *a: ssd_scan.ssd_ragged_scan(*a, impl="pallas",
                                            interpret=False),
        donate_argnums=(6,)).lower(*args).compile()
    hlo = compiled.as_text()
    op = _pallas_op(hlo)
    state_bytes = R * H * P * N * jnp.dtype(state_dtype).itemsize
    assert compiled.memory_analysis().alias_size_in_bytes == state_bytes
    assert not re.search(r"\[%d,%d,%d,%d\]\S* copy\(" % (R, H, P, N), hlo)
    assert [o for o in op["operands"] if len(o[1]) == 4] == \
        [(_SHORT[state_dtype], (R, H, P, N))]
    assert not xk.is_moe_gmm(op, config)
    assert xplane.classify_kernel(op) == ("other_pallas", {})


_SHORT = {"float32": "f32", "bfloat16": "bf16"}

