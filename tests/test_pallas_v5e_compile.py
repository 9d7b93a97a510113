"""The flash and selective-scan kernels through libtpu's real compiler, for
a v5e that is described and not attached (no chip, nothing runs): what the
Pallas interpreter cannot refuse — a slice off the tiling, a strided access
to a buffer that is not 128 wide, more scoped VMEM than a kernel may use —
at every rung the tile chooser returns for the shapes the repo runs.  One
file, and the topology only inside a fixture: one process at a time may load
the TPU's library."""
import functools
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
ss = importlib.import_module("mxnet_tpu.ops.pallas.selective_scan")
sc = importlib.import_module("mxnet_tpu.ops.pallas.short_conv")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a TPU executable cannot be read back from the persistent cache
    # without a chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (B, Tq, Tk, H, D, dtype, causal, the tile the chooser must return)
CASES = [
    pytest.param(4, 2048, 2048, 16, 128, jnp.bfloat16, True, (1024, 1024),
                 id="pythia14_train"),
    pytest.param(4, 2048, 2048, 8, 128, jnp.bfloat16, True, (1024, 1024),
                 id="pythia69_tp4_shard"),
    pytest.param(1, 8192, 8192, 16, 64, jnp.bfloat16, True, (1024, 1024),
                 id="chip_smoke_long_d64"),
    # 8 key/value heads broadcast to the 32 query heads before the kernels
    pytest.param(4, 8192, 8192, 32, 64, jnp.bfloat16, True, (1024, 1024),
                 id="lfm2_8b_train_s8k_d64"),
    pytest.param(2, 1024, 1024, 2, 128, jnp.float32, True, (1024, 1024),
                 id="f32_top_rung"),
    pytest.param(2, 1000, 1000, 2, 64, jnp.float32, False, (1024, 1024),
                 id="ragged_1000_d64"),
    pytest.param(2, 1100, 1100, 2, 128, jnp.float32, True, (128, 128),
                 id="ragged_1100_tile_does_not_divide"),
    pytest.param(2, 1536, 1536, 2, 128, jnp.bfloat16, True, (512, 512),
                 id="rung_512"),
    pytest.param(2, 768, 768, 2, 128, jnp.bfloat16, True, (256, 256),
                 id="rung_256"),
    pytest.param(2, 100, 100, 2, 64, jnp.float32, True, (104, 104),
                 id="sub_tile_100"),
    pytest.param(2, 512, 1024, 2, 128, jnp.bfloat16, True, (512, 1024),
                 id="ring_step_tq_ne_tk"),
]


@pytest.mark.parametrize("B,Tq,Tk,H,D,dtype,causal,tile", CASES)
def test_flash_kernels_compile_for_v5e(one_chip, B, Tq, Tk, H, D, dtype,
                                       causal, tile):
    assert fa._choose_tiles(Tq, Tk, D, jnp.dtype(dtype).itemsize) == \
        (tile,) * 3

    def fwd_and_grads(q, k, v, do, dlse):
        # both outputs and both cotangents: the three kernels, and the lse
        # cotangent's fold into delta
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_lse(
            q, k, v, causal=causal, interpret=False), q, k, v)
        return out, vjp((do, dlse))

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = spec((B, Tq, H, D), dtype)
    kv = spec((B, Tk, H, D), dtype)
    # as chip_smoke.py's parity leg runs them: float32 at `highest` (the
    # suite's own default), bfloat16 at the default precision the models
    # use (Mosaic takes no multi-pass precision on bfloat16 operands)
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        compiled = jax.jit(fwd_and_grads).lower(
            q, kv, kv, q, spec((B, H, Tq), jnp.float32)).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert name in text, name


# (batch, T, Di, N, dtype of u / z / B / C, the blocks the chooser must return)
SCAN_CASES = [
    pytest.param(1, 4096, 5120, 16, jnp.bfloat16, (128, 1024),
                 id="jamba2_3b_train_one_of_14_layers"),
    pytest.param(1, 4096, 2048, 16, jnp.bfloat16, (128, 1024),
                 id="chip_smoke_hybrid"),
    pytest.param(1, 2048, 1536, 16, jnp.bfloat16, (256, 512),
                 id="rung_512_two_tiles_of_columns_a_chunk"),
    pytest.param(2, 1100, 640, 16, jnp.float32, (256, 128),
                 id="f32_ragged_1100_di640"),
    pytest.param(2, 300, 256, 4, jnp.float32, (256, 256),
                 id="f32_n4_padded_to_a_sublane_tile"),
    pytest.param(1, 2048, 2048, 8, jnp.bfloat16, (256, 1024),
                 id="n8_one_sublane_tile"),
    pytest.param(1, 1024, 1024, 24, jnp.bfloat16, (64, 1024),
                 id="n24_three_sublane_tiles"),
]


def _compile_scan(one_chip, Bt, T, Di, N, dtype, **blocks):
    def fwd_and_grads(u, delta, A, B, C, D, z, do):
        out, vjp = jax.vjp(lambda *a: ss.selective_scan(
            *a, interpret=False, **blocks), u, delta, A, B, C, D, z)
        return out, vjp(do)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    wide, narrow = spec((Bt, T, Di), dtype), spec((Bt, T, N), dtype)
    return jax.jit(fwd_and_grads).lower(
        wide, spec((Bt, T, Di), jnp.float32), spec((Di, N), jnp.float32),
        narrow, narrow, spec((Di,), jnp.float32), wide, wide
    ).compile().as_text()


def _scan_calls(text):
    """The two kernels' custom calls in the compiled program, by name."""
    calls = {}
    for line in text.splitlines():
        for name in ("ssm_scan_fwd", "ssm_scan_bwd"):
            if "custom-call(" in line and "%" + name in line.split("=")[0]:
                calls[name] = line
    return calls


@pytest.mark.parametrize("Bt,T,Di,N,dtype,blocks", SCAN_CASES)
def test_scan_kernels_compile_for_v5e(one_chip, Bt, T, Di, N, dtype, blocks):
    assert ss._choose_blocks(T, Di, N) == blocks
    calls = _scan_calls(_compile_scan(one_chip, Bt, T, Di, N, dtype))
    assert sorted(calls) == ["ssm_scan_bwd", "ssm_scan_fwd"]
    # what the benchmark's reader finds the scan's calls by
    # (``family_hybrid_ssm_lm.scan_call_seconds``): the decay matrix
    # states-first, a float32 [N, Di], an operand of both kernels
    n_p, di_p = -(-N // 8) * 8, -(-Di // blocks[1]) * blocks[1]
    for name, line in calls.items():
        operands = line.split("custom-call(", 1)[1]
        assert "f32[%d,%d]" % (n_p, di_p) in operands, (name, operands[:400])


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def test_scan_kernels_spread_a_row_by_a_load_of_stride_0():
    """``_row`` is the one place where ``interpret`` forks the kernels: the
    tests off the chip take its ``broadcast_to`` branch.  What is compiled
    above reads a step's row of ``delta`` and ``delta u`` (and, backwards,
    ``dy``) by a load of stride 0, on every step and lane group: two a step
    in the forward's loop, three in each of the backward's two."""
    Bt, T, Di, N = 1, 256, 512, 16
    chunk, d_block = ss._choose_blocks(T, Di, N)
    groups = d_block // 128

    def fwd_and_grads(u, delta, A, B, C, D, z, do):
        out, vjp = jax.vjp(lambda *a: ss.selective_scan(
            *a, interpret=False), u, delta, A, B, C, D, z)
        return out, vjp(do)

    wide = jax.ShapeDtypeStruct((Bt, T, Di), jnp.bfloat16)
    narrow = jax.ShapeDtypeStruct((Bt, T, N), jnp.bfloat16)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    traced = jax.make_jaxpr(fwd_and_grads)(
        wide, f32((Bt, T, Di)), f32((Di, N)), narrow, narrow, f32((Di,)),
        wide, wide)
    found = {}
    for call in _equations(traced.jaxpr):
        if call.primitive.name != "pallas_call":
            continue
        loads = 0
        for eqn in _equations(call.params["jaxpr"]):
            if eqn.primitive.name == "get":
                index = jax.tree_util.tree_unflatten(eqn.params["tree"],
                                                     eqn.invars[1:])
                loads += repr(index).count("stride=0")
        found[call.params["name"]] = loads
    assert found == {"ssm_scan_fwd": 2 * ss._ROWS * groups,
                     "ssm_scan_bwd": 6 * ss._ROWS * groups}


# (configuration, traffic, the layers of the cut, custom calls by kernel)
LM_CASES = [
    pytest.param("pythia-1.4b-sizes", "pretrain_b4_s2048", 2,
                 {"flash_fwd": 2, "flash_dq": 2, "flash_dkv": 2},
                 id="pythia14_train_2_layers"),
    pytest.param("jamba2-3b-l14", "pretrain_b1_s4096", ("mamba", "attention"),
                 {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1,
                  "ssm_scan_fwd": 1, "ssm_scan_bwd": 1},
                 id="jamba2_3b_train_mamba_and_attention"),
    # an expert layer's row gathers: its sources packed (the tokens twice,
    # ``g``, the down rows, the dispatch's gradient), the dispatch's rows
    # made again in the second forward, a slot sum each way, one backward
    pytest.param("smallthinker-21b-l4-e16", "pretrain_b1_s16384_ep4", 2,
                 {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1,
                  "flash_fwd_window": 1, "flash_dq_window": 1,
                  "flash_dkv_window": 1, "moe_pack": 10,
                  "moe_gather_rows": 4, "moe_slot_sum": 4,
                  "moe_gather_grad": 2},
                 id="smallthinker_train_s16k_full_and_window"),
    # the convolution's forward runs again in the second forward: its
    # result is not kept, ``in_proj``'s output is
    pytest.param("lfm2-8b-a1b-l5-e8", "pretrain_b4_s8192_ep4",
                 ("conv", "attention"),
                 {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1,
                  "short_conv_fwd": 2, "short_conv_bwd": 1, "moe_pack": 5,
                  "moe_gather_rows": 2, "moe_slot_sum": 2,
                  "moe_gather_grad": 1},
                 id="lfm2_8b_train_s8k_conv_and_attention"),
]


@pytest.mark.parametrize("config,traffic,layers,calls", LM_CASES)
def test_lm_train_step_runs_each_kept_kernel_once_on_v5e(
        one_chip, monkeypatch, config, traffic, layers, calls):
    """The training step of each LM cell, cut to two layers at the cell's
    batch and widths, through libtpu's compiler: what the rematerialised
    layer keeps by name (``models.transformer.KEPT``) reaches through
    ``custom_vjp`` and the compiler, so the optimized program holds one
    flash forward an attention layer and one scan forward a Mamba layer,
    not two (the backward's kernels once, as ever)."""
    from mxnet_tpu.models import TransformerConfig, TransformerLM
    from mxnet_tpu.models.transformer import make_train_step
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(root, "configs", config + ".json")) as f:
        m = json.load(f)["model"]
    with open(os.path.join(root, "traffic", traffic + ".json")) as f:
        t = json.load(f)
    if isinstance(layers, tuple):
        m.update(layer_types=layers, n_layers=len(layers))
    else:
        m.update(n_layers=layers)
    for key in ("attn_windows", "attn_rope", "mlp_types"):  # one a layer
        if key in m:
            m[key] = m[key][:m["n_layers"]]
    # the kernel rule sees the CPU this test runs on: tell it the backend
    # the program is compiled for
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(TransformerConfig(**m))
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((t["batch"], t["seq"]), jnp.int32,
                               sharding=one_chip)
    # the precision the cells run at, not the suite's ``highest``
    with jax.default_matmul_precision("default"):
        text = jax.jit(make_train_step(model), donate_argnums=(0, 1)).lower(
            state, state, tok, tok).compile().as_text()
    found = {}
    for name in re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*custom-call\([^\n]*"
                           r"tpu_custom_call", text):
        found[name] = found.get(name, 0) + 1
    assert {k: found.get(k, 0) for k in calls} == calls, found


def test_flash_at_a_value_width_of_its_own_compiles_for_v5e(one_chip):
    """Latent attention's shape in the expert cell: scores 192 wide, values
    128, 1 x 16 heads x 4096, bfloat16: the three kernels keep the top rung."""
    B, T, H, D, Dv = 1, 4096, 16, 192, 128
    assert fa._choose_tiles(T, T, D, 2, Dv) == ((1024, 1024),) * 3
    qk = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=one_chip)
    vo = jax.ShapeDtypeStruct((B, T, H, Dv), jnp.bfloat16, sharding=one_chip)

    def fwd_and_grads(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, scale=0.1147, interpret=False), q, k, v)
        return out, vjp(do)

    # bfloat16 at the default precision the models use (Mosaic takes no
    # multi-pass precision on bfloat16 operands)
    with jax.default_matmul_precision("default"):
        text = jax.jit(fwd_and_grads).trace(qk, qk, vo, vo).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 3


@pytest.mark.parametrize("T,W", [
    pytest.param(16384, 4096, id="smallthinker_train_s16k_window_layer"),
    pytest.param(4096, 1000, id="band_edge_inside_a_tile"),
    pytest.param(1100, 300, id="ragged_1100_128_tiles")])
def test_flash_with_a_window_compiles_for_v5e(one_chip, T, W):
    """The three kernels with a band at the window-and-full-attention
    cell's shape (1 x 28 heads x 16,384 x 128, bfloat16, ``W`` 4,096) and
    where the band's edges fall inside tiles: named for the window, the
    index maps' clamps at both ends taken by Mosaic."""
    B, H, D = 1, 28 if T == 16384 else 2, 128
    if T == 16384:
        assert fa._choose_tiles(T, T, D, 2) == ((1024, 1024),) * 3
    x = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=one_chip)

    def fwd_and_grads(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=W, interpret=False), q, k, v)
        return out, vjp(do)

    with jax.default_matmul_precision("default"):
        text = jax.jit(fwd_and_grads).trace(x, x, x, x).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    for name in ("flash_fwd_window", "flash_dq_window", "flash_dkv_window"):
        assert name in text, name


@pytest.mark.parametrize("M,K,N,G,tiles", [
    pytest.param(24576, 2048, 1408, 32,
                 {"gmm_fwd": (2048, 1408), "gmm_dx": (1408, 2048),
                  "gmm_dw": (1024, 1408)}, id="dsv2_lite_gate_up"),
    pytest.param(24576, 1408, 2048, 32,
                 {"gmm_fwd": (1408, 2048), "gmm_dx": (2048, 1408),
                  "gmm_dw": (1408, 1024)}, id="dsv2_lite_down"),
    pytest.param(1000, 96, 40, 3,
                 {"gmm_fwd": (96, 40), "gmm_dx": (40, 96),
                  "gmm_dw": (96, 40)}, id="rows_and_widths_off_the_tiling"),
])
def test_grouped_product_kernels_compile_for_v5e(one_chip, M, K, N, G, tiles):
    """Forward, ``dx`` and ``dW`` of the grouped product at the expert
    cell's two shapes (the whole contraction and output width one block in
    forward and ``dx``), and at sizes nothing divides."""
    gm = importlib.import_module("mxnet_tpu.ops.pallas.grouped_matmul")
    dtype = jnp.bfloat16 if M > 1000 else jnp.float32
    size = jnp.dtype(dtype).itemsize
    for kernel, (tk, tn) in tiles.items():
        k, n = (N, K) if kernel == "gmm_dx" else (K, N)
        assert gm._choose_tiles(kernel, M, k, n, size)[1:] == (tk, tn), kernel
    x = jax.ShapeDtypeStruct((M, K), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((G, K, N), dtype, sharding=one_chip)
    dy = jax.ShapeDtypeStruct((M, N), dtype, sharding=one_chip)
    gs = jax.ShapeDtypeStruct((G,), jnp.int32, sharding=one_chip)

    def fwd_and_grads(x, w, gs, dy):
        out, vjp = jax.vjp(lambda x, w: gm.grouped_matmul(
            x, w, gs, interpret=False), x, w)
        return out, vjp(dy)

    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        text = jax.jit(fwd_and_grads).trace(x, w, gs, dy).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 3


@pytest.mark.parametrize("B,T,D,K,dtype,tiles", [
    pytest.param(4, 8192, 2048, 3, jnp.bfloat16, (512, 512),
                 id="lfm2_8b_train_s8k"),
    pytest.param(2, 1000, 256, 3, jnp.float32, (8, 256),
                 id="f32_1000_rows_of_8"),
    pytest.param(1, 37, 64, 9, jnp.float32, (8, 64),
                 id="padded_37_taps_9_narrow"),
])
def test_short_conv_kernels_compile_for_v5e(one_chip, B, T, D, K, dtype,
                                            tiles):
    """The gated convolution's forward and backward at the LFM2 cell's
    shape and where the tiles are a sublane tile, the halo whole, the
    channels narrower than the lanes: the halo blocks' index maps, the
    taps' loads at offsets off the sublane tile and the partial ``dw``
    rows taken by Mosaic."""
    assert sc._choose_tiles(-(-T // 8) * 8, D) == tiles
    x = jax.ShapeDtypeStruct((B, T, D), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((K, D), dtype, sharding=one_chip)

    def fwd_and_grads(b, c, u, w, dy):
        out, vjp = jax.vjp(lambda *a: sc.gated_short_conv(
            *a, interpret=False), b, c, u, w)
        return out, vjp(dy)

    text = jax.jit(fwd_and_grads).trace(x, x, x, w, x).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    for name in ("short_conv_fwd", "short_conv_bwd"):
        assert re.search(r"%%%s(\.\d+)? = [^\n]*tpu_custom_call" % name,
                         text), name


# (tokens, slots, width): the three expert cells' [P, E] and [S, E]
@pytest.mark.parametrize("S,k,E", [
    pytest.param(32768, 4, 2048, id="lfm2_8b_train_s8k"),
    pytest.param(16384, 6, 2560, id="smallthinker_train_s16k"),
    pytest.param(4096, 6, 2048, id="dsv2_lite_train")])
def test_row_gather_kernels_compile_for_v5e(one_chip, S, k, E):
    """The expert layer's four row-gather kernels at the cells' shapes:
    a row of a packed source as one DMA, the scalar-prefetched index
    vectors (``[P]`` int32), the blocks' scratch within VMEM."""
    mg = importlib.import_module("mxnet_tpu.ops.pallas.moe_gather")
    assert mg._takes(S, k, E, jnp.bfloat16, False)
    P = S * k

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def gathers(tokens, idx, inverse, live, out, weights, g, w_rows):
        return (mg.sorted_rows(tokens, idx, live),
                mg.slot_sum(out, inverse, live, weights, k),
                mg.sorted_rows_grad(g, idx, live, out, w_rows))

    text = jax.jit(gathers).trace(
        spec((S, E), jnp.bfloat16), spec((P,), jnp.int32),
        spec((P,), jnp.int32), spec((1,), jnp.int32),
        spec((P, E), jnp.bfloat16), spec((S, k), jnp.float32),
        spec((S, E), jnp.bfloat16), spec((P,), jnp.float32)).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    found = {}
    for name in re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*custom-call\([^\n]*"
                           r"tpu_custom_call", text):
        found[name] = found.get(name, 0) + 1
    assert found == {"moe_pack": 3, "moe_gather_rows": 1, "moe_slot_sum": 1,
                     "moe_gather_grad": 1}, found
