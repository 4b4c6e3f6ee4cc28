"""The main path's Pallas kernels, compiled for a TPU v5e that is described
and not attached (on-chip-measurement guide, section 2).

Interpret-mode tests cannot see what Mosaic refuses — a slice not aligned
to the (8, 128) tiling, more VMEM than a kernel may use. These compile each
kernel at the real width it has in ResNet-50 training and BERT-base serving
with `interpret=False` passed explicitly (under JAX_PLATFORMS=cpu the
kernels would otherwise pick the interpreter) and assert the kernel is in
the compiled program. A compile that passes is not a chip run: nothing
executes here.

Only one process may hold libtpu, so the topology is described inside a
fixture of THIS file (never at import, in a skipif or in conftest.py) and
the compile runs in the test's own process.
"""
import functools
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for_chip(one_chip):
    """compile(fn, *shape_dtype_pairs) -> compiled text, with jax's
    persistent compile cache off around it: an entry written for a
    described chip cannot be read back without one, and the next run
    would warn and recompile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        # production numerics: conftest's "highest" matmul precision is
        # for the CPU oracles, not for what the chip would compile
        with jax.default_matmul_precision("default"):
            return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


bf16, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8

# BERT-base attention: 12 heads of width 64; training batch 32 at seq 128,
# serving 8 slots with cache rungs up to 512, pages of 16 rows. The decode
# cache operands are rows major, hidden (12 x 64 = 768) minor.
_QKV_TRAIN = ((32, 12, 128, 64), bf16)
_QKV_PREFILL = ((8, 12, 512, 64), bf16)
_POOL = ((257, 16, 768), bf16)           # 8 slots x 512 rows + null page


def _flash_fwd(q, k, v, m):
    from deeplearning4j_tpu.kernels import flash_attention
    return flash_attention(q, k, v, kv_mask=m, interpret=False)


def _flash_fwd_bwd(q, k, v, m):
    return jax.grad(lambda *a: _flash_fwd(*a, m).astype(f32).sum(),
                    argnums=(0, 1, 2))(q, k, v)


def _flash_causal(q, k, v):
    from deeplearning4j_tpu.kernels import flash_attention
    return flash_attention(q, k, v, causal=True, interpret=False)


def _decode(q, k, v, m):
    from deeplearning4j_tpu.kernels import flash_attention_decode
    return flash_attention_decode(q, k, v, m, impl="pallas",
                                  interpret=False)


def _decode_paged(q, kp, vp, ptab, m):
    from deeplearning4j_tpu.kernels import flash_attention_decode_paged
    return flash_attention_decode_paged(q, kp, vp, ptab, m, impl="pallas",
                                        interpret=False)


def _routed_experts(x, scores, bias, w1, w2, impl="pallas",
                    interpret=False):
    from deeplearning4j_tpu.models.nemotron_h import relu2
    from deeplearning4j_tpu.parallel.moe import routed_experts
    return routed_experts(x, scores, bias, w1, w2, (0, w1.shape[0]), 22,
                          5.0, relu2, impl=impl, interpret=interpret)


def _experts_specs(rows, latent=1024, width=2688, held=128):
    return [((rows, latent), bf16), ((rows, 512), f32), ((512,), f32),
            ((held, latent, width), bf16), ((held, width, latent), bf16)]


def _layernorm(x, g, b):
    from deeplearning4j_tpu.kernels import fused_layernorm
    return fused_layernorm(x, g, b, 1e-12, 128, False)


def _layernorm_grad(x, g, b):
    return jax.grad(lambda *a: _layernorm(*a).astype(f32).sum(),
                    argnums=(0, 1, 2))(x, g, b)


def _matmul_stats(x, w):
    from deeplearning4j_tpu.kernels.pointwise_conv import matmul_stats
    return matmul_stats(x, w, interpret=False)


def _conv1x1_bn_fwd_bwd(x, w, g, b):
    from deeplearning4j_tpu.kernels.pointwise_conv import fused_conv1x1_bn

    def loss(x, w, g, b):
        out = fused_conv1x1_bn(x, w, g, b, 1e-5, "relu", False)
        return out[0].astype(f32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(x, w, g, b)


def _epilogue(x, w, s, t, r):
    from deeplearning4j_tpu.kernels import matmul_epilogue
    return matmul_epilogue(x, w, s, t, residual=r, act="relu",
                           interpret=False)


def _epilogue_int8(x, w, s, t, r):
    from deeplearning4j_tpu.kernels import int8_matmul_epilogue
    return int8_matmul_epilogue(x, w, s, t, residual=r, act="relu",
                                out_dtype=bf16, interpret=False)


def _conv_specs(m, k, n):
    return [((m, k), bf16), ((k, n), bf16), ((n,), f32), ((n,), f32)]


_M1 = 64 * 56 * 56      # ResNet-50 stage-1 rows at batch 64
_M4 = 64 * 7 * 7        # stage-4 rows

#: (id, fn, argument (shape, dtype) pairs, kernel expected in the program)
CASES = [
    ("flash_fwd_mask_b32_t128", _flash_fwd,
     [_QKV_TRAIN] * 3 + [((32, 128), i32)], True),
    ("flash_fwd_bwd_mask_b32_t128", _flash_fwd_bwd,
     [_QKV_TRAIN] * 3 + [((32, 128), i32)], True),
    ("flash_causal_prefill_b8_t512", _flash_causal, [_QKV_PREFILL] * 3,
     True),
    ("decode_pallas_c512_bf16", _decode,
     [((8, 12, 64), bf16)] + [((8, 512, 768), bf16)] * 2
     + [((8, 512), i32)], True),
    ("decode_pallas_c512_f32", _decode,
     [((8, 12, 64), f32)] + [((8, 512, 768), f32)] * 2
     + [((8, 512), i32)], True),
    # grouped-query decode at the widths of nemotron3_super_serve_decode:
    # 32 query heads over 2 KV heads of 128, 128 slots at a 1024-row rung
    ("decode_pallas_gqa_32q_2kv_c1024_bf16", _decode,
     [((128, 32, 128), bf16)] + [((128, 1024, 256), bf16)] * 2
     + [((128, 1024), i32)], True),
    # its expert layer: 22 choices a row over the 128 experts held, both
    # products in kernels/grouped_matmul.py's `grouped_mlp` with an
    # expert's two 5.5 MB blocks whole in VMEM. 128 rows are the decode
    # step's slots and the short prompt bucket's admit (16-row tiles), 512
    # the long bucket's admit (64-row tiles)
    ("routed_experts_128x22_of_128_held", _routed_experts,
     _experts_specs(128), True),
    ("routed_experts_512x22_of_128_held", _routed_experts,
     _experts_specs(512), True),
    ("decode_paged_pool257_ps16", _decode_paged,
     [((8, 12, 64), bf16), _POOL, _POOL, ((8, 32), i32), ((8, 512), i32)],
     True),
    ("layernorm_fwd_4096x768", _layernorm,
     [((4096, 768), bf16), ((768,), f32), ((768,), f32)], True),
    # under autodiff both VJP rules (kernels/layernorm.py) are plain jnp:
    # the Pallas kernel is the inference path and is not in this program
    ("layernorm_grad_4096x768", _layernorm_grad,
     [((4096, 768), bf16), ((768,), f32), ((768,), f32)], False),
    ("matmul_stats_stage1", _matmul_stats,
     [((_M1, 64), bf16), ((64, 256), bf16)], True),
    ("conv1x1_bn_fwd_bwd_stage1_64to256", _conv1x1_bn_fwd_bwd,
     _conv_specs(_M1, 64, 256), True),
    ("conv1x1_bn_fwd_bwd_stage4_2048to512", _conv1x1_bn_fwd_bwd,
     _conv_specs(_M4, 2048, 512), True),
    ("matmul_epilogue_stage1", _epilogue,
     _conv_specs(_M1, 64, 256) + [((_M1, 256), bf16)], True),
    ("int8_matmul_epilogue_stage1", _epilogue_int8,
     [((_M1, 64), i8), ((64, 256), i8), ((256,), f32), ((256,), f32),
      ((_M1, 256), bf16)], True),
]


@pytest.mark.parametrize("fn,specs,has_kernel",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(compile_for_chip, fn, specs, has_kernel):
    text = compile_for_chip(fn, *specs)
    assert ("tpu_custom_call" in text) == has_kernel
    # XLA's own grouped product is a custom call too: it is in none of these
    assert "ragged-dot" not in text


@pytest.mark.parametrize("rows", [128, 512])
def test_expert_layer_takes_its_kernel_on_a_tpu_for_v5e(
        compile_for_chip, monkeypatch, rows):
    """No silent fallback at the published widths: left to itself on a TPU
    backend, `routed_experts` compiles the Pallas kernel, named for the
    trace, and no `lax.ragged_dot`; at widths Mosaic cannot take it
    compiles `lax.ragged_dot`."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    left_alone = functools.partial(_routed_experts, impl="auto",
                                   interpret=None)
    text = compile_for_chip(left_alone, *_experts_specs(rows))
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    assert sum(c.lstrip().lstrip("%").startswith("grouped_mlp")
               for c in calls) == 1
    assert "ragged-dot" not in text
    toy = compile_for_chip(left_alone, *_experts_specs(rows, 32, 84, 16))
    assert "ragged-dot" in toy and "grouped_mlp" not in toy


def test_decode_kernel_carries_its_names_for_v5e(compile_for_chip):
    """What a profiler trace on the chip tells the decode kernel by: the
    custom call is named after the `pallas_call`'s `name`, under the entry
    point's scope (benchmarks/readers/program_span.py reads both)."""
    text = compile_for_chip(_decode, ((8, 12, 64), f32),
                            *[((8, 512, 768), f32)] * 2,
                            ((8, 512), i32))
    call, = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    assert call.lstrip().lstrip("%").startswith("flash_fwd")
    assert "flash_decode/flash_fwd" in call


# --- the decode superstep as `bert_serve_decode` runs it --------------------
_SLOTS, _RUNG = 64, 512


def _superstep_for_v5e(one_chip, dtype):
    """scan(BertDecoder.step + sample_step, length=1) over BERT-base with
    the cache donated, compiled for the described chip. -> (compiled,
    elements of one cache leaf)"""
    import importlib

    from jax import lax

    from deeplearning4j_tpu.generation.decode import BertDecoder
    from deeplearning4j_tpu.generation.sampling import sample_step
    from deeplearning4j_tpu.models.bert import BertConfig, init_bert_params

    cfg = BertConfig(dtype=dtype)
    params = jax.eval_shape(lambda k: init_bert_params(cfg, k),
                            jax.random.PRNGKey(0))
    dec = BertDecoder(cfg, params, attn_impl="pallas")
    cache = jax.eval_shape(lambda: dec.init_cache(_SLOTS, _RUNG))

    def superstep(params, cache, tokens, pos, rng, method, temp, topk):
        def body(carry, _):
            cache, tokens, pos, rng = carry
            logits, cache = dec.step((params,), cache, tokens, pos)
            tok, rng = sample_step(logits, rng, method, temp, topk)
            return (cache, tok, pos + 1, rng), tok
        return lax.scan(body, (cache, tokens, pos, rng), None, length=1)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=one_chip), tree)

    slot = jax.ShapeDtypeStruct((_SLOTS,), i32)
    args = on_chip((params, cache, slot, slot,
                    jax.ShapeDtypeStruct((_SLOTS, 2), jnp.uint32), slot,
                    jax.ShapeDtypeStruct((_SLOTS,), f32), slot))
    # under JAX_PLATFORMS=cpu the kernel would pick the interpreter: steer
    # it from here, not through an option of the program
    fa = importlib.import_module(
        "deeplearning4j_tpu.kernels.flash_attention")
    real = fa._flash_decode
    fa._flash_decode = lambda q, k, v, m, bk, _, *n: real(q, k, v, m, bk,
                                                          False, *n)
    try:
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(superstep, donate_argnums=(1, 2, 3, 4)) \
                .lower(*args).compile()
    finally:
        fa._flash_decode = real
    return compiled, _SLOTS * _RUNG * cfg.hidden_size


@pytest.fixture(scope="module")
def superstep_for_v5e(compile_for_chip, one_chip):
    """dtype -> `_superstep_for_v5e`'s result, compiled once a dtype.
    `compile_for_chip` is asked for only to keep jax's persistent cache
    off around the compiles."""
    return functools.cache(lambda dtype: _superstep_for_v5e(one_chip, dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_superstep_keeps_the_cache_in_place_for_v5e(
        superstep_for_v5e, dtype):
    """The cell's decode program moves no cache: every `(S, C, H·Dh)` leaf
    has one layout at entry, in the row write, in the kernel and at exit
    (a cache-sized copy or slice costs 0.7 ms a leaf a step on the chip:
    `PERF.md`, PR 27). And each layer's kernel reads the rows in use, not
    the rung (PR 38): it takes the slots' lengths as a grid over the tiles
    in use, of `decode_tile_rows(512, 768, dtype)` rows each."""
    from deeplearning4j_tpu.kernels.flash_attention import decode_tile_rows
    compiled, leaf = superstep_for_v5e(dtype)
    text = compiled.as_text()
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"(copy|slice|dynamic-slice|transpose)\(", line)
        if m and m.group(1):
            n = 1
            for d in m.group(1).split(","):
                n *= int(d)
            if n >= leaf:
                moved.append(line.strip()[:160])
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    assert len(calls) == 12
    for li in range(12):
        assert sum(f"layer{li}/attn/flash_decode/flash_fwd" in c
                   and c.lstrip().lstrip("%").startswith("flash_fwd")
                   for c in calls) == 1, li
        assert f"layer{li}/kv_write" in text
    # the call's operands, as the compiled program lays them out: the
    # grid's extent, a step's slot and its tile for every step there may
    # be (slots x rung / tile of them), a slot's tiles in use, then q, the
    # K and V leaves whole and the mask
    tile = decode_tile_rows(_RUNG, 768, jnp.dtype(dtype))
    assert 128 <= tile < _RUNG
    steps = _SLOTS * _RUNG // tile
    short = {"float32": "f32", "bfloat16": "bf16"}[dtype]
    want = [r"s32\[\]", rf"s32\[{steps}\]", rf"s32\[{steps}\]",
            rf"s32\[{_SLOTS}\]", rf"{short}\[{_SLOTS},1,768\]",
            rf"{short}\[{_SLOTS},{_RUNG},768\]",
            rf"{short}\[{_SLOTS},{_RUNG},768\]",
            rf"s32\[{_SLOTS},1,{_RUNG}\]"]
    for call in calls:
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                             call).group(1)
        got = re.split(r", (?=\w+\[)", operands)
        assert len(got) == len(want) and all(
            re.match(w, g) for w, g in zip(want, got)), operands


def test_hybrid_superstep_is_the_program_it_was_for_v5e(compile_for_chip,
                                                        one_chip):
    """`NemotronHDecoder.step` calls the decode kernel WITHOUT lengths, and
    what PR 38 did to the kernel under lengths left that call alone: a
    toy-size superstep lowered for the described chip is, outside its one
    kernel, the text it was, and the kernel's Mosaic module, printed
    without source locations, the module it was (both as sha256 of the
    parent commit's; a PR that changes the hybrid's step or the kernel's
    plain form on purpose prints the new ones from here)."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    from deeplearning4j_tpu.generation.decode import NemotronHDecoder
    from deeplearning4j_tpu.models import nemotron_h as nh

    cfg = nh.NemotronHConfig.from_dict(dict(
        vocab_size=96, hidden_size=64, hybrid_override_pattern="*EMEM",
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        conv_kernel=4, chunk_size=8, n_routed_experts=16,
        num_experts_per_tok=4, moe_latent_size=32, moe_intermediate_size=84,
        moe_shared_expert_intermediate_size=84, routed_scaling_factor=5,
        norm_eps=1e-5, time_step_min=0.001, time_step_max=0.1,
        time_step_floor=1e-4, num_hidden_layers=5,
        held={"pattern": "*EMEM", "experts": [0, 16]}))
    slots, rung = 4, 1024
    params = jax.eval_shape(lambda k: nh.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dec = NemotronHDecoder(cfg, params, attn_impl="pallas")
    cache = jax.eval_shape(lambda: dec.init_cache(slots, rung))

    text = _lower_one_step_superstep(dec, params, cache, slots,
                                     one_chip).as_text()
    body = r'\\22body\\22: \\22([^\\]*)\\22'
    kernel, = re.findall(body, text)
    context = jax_mlir.make_ir_context()
    context.allow_unregistered_dialects = True
    with context:
        module = ir.Module.parse(base64.b64decode(kernel)) \
            .operation.get_asm(enable_debug_info=False)

    def digest(s):
        return hashlib.sha256(s.encode()).hexdigest()[:16]

    got = (digest(re.sub(body, "", text)), digest(module))
    assert got == ("fc77e0b076917756", "9c4683b0a83dbc25"), got


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_superstep_sorts_no_vocabulary_for_v5e(superstep_for_v5e,
                                                      dtype):
    """Top-k's threshold comes from a selection (`sampling.kth_largest`):
    the decode program holds no `sort` as wide as the vocabulary (the full
    sort of 64 x 30522 logits was 2.07 ms of a 5.99 ms step on the chip:
    `PERF.md`, PR 29)."""
    from deeplearning4j_tpu.models.bert import BertConfig
    vocab = BertConfig().vocab_size
    text = superstep_for_v5e(dtype)[0].as_text()
    assert "sample/select" in text
    wide = [line.strip()[:160] for line in text.splitlines()
            if re.search(r"\bsort\(", line)
            and re.search(rf"\[(\d+,)*{vocab}[,\]]", line)]
    assert not wide, wide


# Keye-VL-2.0 serving (PR 35): 32 query heads over 4 KV heads of 128, an
# indexer of 16 heads of 64 over one key head, 16 held SwiGLU experts of
# 2048 -> 768 -> 2048; 32 slots at an 18432-row rung, prompts of 16384 in
# query blocks of 4096.
def _keye_index_scores(q, k, w):
    from deeplearning4j_tpu.kernels.indexer import index_scores
    return index_scores(q, k, w, 1 / 32., q_offset=12288, impl="pallas",
                        interpret=False)


def _keye_index_scores_decode(q, packed, w, pos):
    from deeplearning4j_tpu.kernels.indexer import index_scores_decode
    return index_scores_decode(q, packed, w, pos, 1 / 32., impl="pallas",
                               interpret=False)


def _keye_selected_attention(q, k, v, sel):
    from deeplearning4j_tpu.kernels.flash_attention import \
        flash_attention_selected
    return flash_attention_selected(q, k, v, sel, 4, q_offset=12288,
                                    impl="pallas", interpret=False)


def _keye_gathered_decode(q, k, v, m):
    from deeplearning4j_tpu.kernels import flash_attention_decode
    return flash_attention_decode(q, k, v, m, impl="pallas",
                                  interpret=False)


def _keye_decode_in_place(q, k, v, m, n):
    from deeplearning4j_tpu.kernels import flash_attention_decode
    return flash_attention_decode(q, k, v, m, impl="pallas",
                                  interpret=False, lengths=n)


def _keye_gated_experts(x, w_gate, w_up, w_down, groups, weights):
    from deeplearning4j_tpu.kernels.grouped_matmul import grouped_mlp
    return grouped_mlp(x, w_up, w_down, groups, jax.nn.silu,
                       rows=jnp.arange(groups.shape[0]) // 8,
                       weights=weights, w_gate=w_gate, interpret=False)


_KEYE_EXPERTS = (((16, 2048, 768), bf16), ((16, 2048, 768), bf16),
                 ((16, 768, 2048), bf16))


@pytest.mark.parametrize("fn,specs,name", [
    (_keye_index_scores, (((16, 4096, 64), bf16), ((16384, 64), bf16),
                          ((4096, 16), f32)), "index_scores"),
    (_keye_index_scores_decode, (((32, 16, 64), bf16),
                                 ((32, 9216, 128), bf16), ((32, 16), f32),
                                 ((32,), i32)), "index_scores_decode"),
    (_keye_selected_attention, (((4096, 4096), bf16), ((16384, 512), bf16),
                                ((16384, 512), bf16), ((4096, 16384), i8)),
     "flash_selected"),
    (_keye_gathered_decode, (((32, 32, 128), bf16), ((32, 2048, 512), bf16),
                             ((32, 2048, 512), bf16),
                             ((32, 2048), jnp.bool_)), "flash_fwd"),
    (_keye_decode_in_place, (((32, 32, 128), bf16),
                             ((32, 18432, 512), bf16),
                             ((32, 18432, 512), bf16),
                             ((32, 18432), jnp.bool_), ((32,), i32)),
     "flash_fwd"),
    (_keye_gated_experts, (((32, 2048), bf16), *_KEYE_EXPERTS,
                           ((256,), i32), ((256,), f32)), "grouped_mlp"),
    (_keye_gated_experts, (((2048, 2048), bf16), *_KEYE_EXPERTS,
                           ((16384,), i32), ((16384,), f32)), "grouped_mlp"),
], ids=["index_scores", "index_scores_decode", "selected_attention",
        "decode_over_the_gathered_rung", "decode_in_place_under_a_selection",
        "gated_experts_a_step",
        "gated_experts_a_prefill_run"])
def test_sparse_attention_kernels_compile_for_v5e(compile_for_chip, fn,
                                                  specs, name):
    """The kernels `KeyeDecoder` adds, at the published widths: the
    indexer's scores over a 4096-row query block of a 16384 prompt and over
    a packed decode leaf, attention under a row-by-row selection, the
    grouped-query decode kernel over a GATHERED rung of 2048 rows and over
    the whole 18432-row leaf in place under a selection mask and the slots'
    lengths (PR 36), and the gated expert (three weight blocks of 3.1 MB,
    double-buffered) whose rows go in and whose weighted results come out
    inside the kernel: no gather is left, and no array of the tiled rows
    (V·tm, K) or of the pairs' results (m, D), both longer than x, is
    anywhere in the program."""
    text = compile_for_chip(fn, *specs)
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    assert len(calls) == 1 and name in calls[0]
    if fn is _keye_gated_experts:
        tokens = specs[0][0][0]
        assert " gather(" not in text
        assert max(int(r) for r in re.findall(r"\[(\d+),2048\]", text)) \
            == tokens


def _expert_layer(top_k, n, gated):
    def layer(x, scores, *weights):
        from deeplearning4j_tpu.parallel.moe import routed_experts
        *gate, w_in, w_out = weights
        return routed_experts(
            x, scores, None, w_in, w_out, (0, n), top_k, 1.0,
            jax.nn.silu if gated else jax.nn.relu, impl="pallas",
            interpret=False, w_gate=gate[0] if gate else None)
    return layer


@pytest.mark.parametrize("tokens,experts,top_k,n,width,ff,gated,calls", [
    (4096, 512, 22, 128, 1024, 2688, False, 2),
    (2048, 128, 8, 16, 4096, 1024, True, 2),
    (77, 128, 8, 16, 2048, 1024, True, 1),
], ids=["hybrid_bucket_4096", "prefill_run_at_width_4096",
        "decode_step_of_77_slots"])
def test_the_expert_layer_compiles_at_its_largest_calls(
        compile_for_chip, tokens, experts, top_k, n, width, ff, gated,
        calls):
    """What one call of the expert kernel holds grows with its tokens (x
    and their sums in VMEM) and its pairs (the layout's tables in SMEM,
    1 MiB on a v5e), so `routed_experts` takes tokens past what a call may
    hold in runs: the hybrid's one prompt of 4096 tokens (90 112 pairs,
    `nemotron_h.moe_mixer` runs it whole) and a 2048-token run at a width
    of 4096 each in two calls; a decode step of a slot count that is no
    multiple of 8 in one."""
    specs = [((tokens, width), bf16), ((tokens, experts), f32),
             *[((n, width, ff), bf16)] * (2 if gated else 1),
             ((n, ff, width), bf16)]
    text = compile_for_chip(_expert_layer(top_k, n, gated), *specs)
    kernels = [l for l in text.splitlines()
               if "tpu_custom_call" in l and " custom-call(" in l]
    assert len(kernels) == calls
    assert all("grouped_mlp" in l for l in kernels)
    assert " gather(" not in text


def _expert_layer_from_a_start(top_k, n):
    def layer(x, scores, start, w_gate, w_in, w_out):
        from deeplearning4j_tpu.parallel.moe import routed_experts
        return routed_experts(
            x, scores, None, w_in, w_out, (0, n), top_k, 2.448, jax.nn.silu,
            impl="pallas", interpret=False, w_gate=w_gate, init=start)
    return layer


@pytest.mark.parametrize("tokens,top_k,width,ff,calls", [
    (2048, 6, 2048, 768, 1),
    (2048, 8, 4096, 1024, 2),
    (77, 6, 2048, 768, 1),
], ids=["kanana2_prefill_run", "prefill_run_at_width_4096",
        "decode_step_of_77_slots"])
def test_the_expert_layer_sums_into_its_start_for_v5e(
        compile_for_chip, tokens, top_k, width, ff, calls):
    """Handed a starting value (a shared expert's output, float32), each
    call of the kernel starts its sum from that run's rows and writes its
    result over them (the output aliases the operand), so nothing adds a
    `(tokens, width)` float32 array after the kernel: the layer's whole sum
    is the kernel's."""
    specs = [((tokens, width), bf16), ((tokens, 128), f32),
             ((tokens, width), f32), *[((16, width, ff), bf16)] * 2,
             ((16, ff, width), bf16)]
    text = compile_for_chip(_expert_layer_from_a_start(top_k, 16), *specs)
    kernels = [l for l in text.splitlines()
               if "tpu_custom_call" in l and " custom-call(" in l]
    assert len(kernels) == calls
    assert all("grouped_mlp" in l and "output_to_operand_aliasing" in l
               for l in kernels)
    assert not re.findall(rf"f32\[{tokens},{width}\]\S* add\(", text)


def _lower_one_step_superstep(dec, params, cache, slots, one_chip):
    """One decode step and its sampling as the server's superstep runs
    them (`lax.scan`, the cache donated), lowered for the described chip
    over shapes alone. The kernels and the expert layer ask
    `jax.default_backend()`: steered from here, not through an option of
    the program."""
    from jax import lax

    from deeplearning4j_tpu.generation.sampling import sample_step

    def superstep(params, cache, tokens, pos, rng, method, temp, topk):
        def body(carry, _):
            cache, tokens, pos, rng = carry
            logits, cache = dec.step((params,), cache, tokens, pos)
            tok, rng = sample_step(logits, rng, method, temp, topk)
            return (cache, tok, pos + 1, rng), tok
        return lax.scan(body, (cache, tokens, pos, rng), None, length=1)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=one_chip), tree)

    slot = jax.ShapeDtypeStruct((slots,), i32)
    args = on_chip((params, cache, slot, slot,
                    jax.ShapeDtypeStruct((slots, 2), jnp.uint32), slot,
                    jax.ShapeDtypeStruct((slots,), f32), slot))
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        with jax.default_matmul_precision("default"):
            return jax.jit(superstep, donate_argnums=(1, 2, 3, 4)) \
                .lower(*args)
    finally:
        jax.default_backend = real


def _compile_one_step_superstep(*args):
    return _lower_one_step_superstep(*args).compile()


def test_sparse_decode_superstep_gathers_no_rows_for_v5e(compile_for_chip,
                                                         one_chip):
    """`KeyeDecoder`'s superstep at the published widths (32 slots, a rung
    of 18432 = 9 x `topk`, 2 layers) attends its kept rows where they lie:
    the program holds no `(32, 2048, 512)` rung of gathered rows (XLA's row
    gather was 19.3 of a 25.0 ms step on the chip: `PERF.md`, PR 36), no
    temporary the size of a K leaf, and one `flash_fwd` a layer under the
    scope the benchmark reads."""
    import json

    from benchmarks.families.keye_vl_serve import model_config
    from deeplearning4j_tpu.generation.decode import KeyeDecoder
    from deeplearning4j_tpu.models import keye_vl

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmarks", "configs",
                           "keye_vl2_30b_a3b_ep8.json")) as f:
        cfg = model_config({**json.load(f), "num_hidden_layers": 2},
                           "bfloat16")
    slots, rung = 32, 18432
    params = jax.eval_shape(lambda k: keye_vl.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dec = KeyeDecoder(cfg, params, attn_impl="pallas")
    cache = jax.eval_shape(lambda: dec.init_cache(slots, rung))

    compiled = _compile_one_step_superstep(dec, params, cache, slots,
                                           one_chip)
    text = compiled.as_text()
    gathered = [line.strip()[:160] for line in text.splitlines()
                if re.search(r"bf16\[(32,2048,512|65536,512)\]", line)]
    assert not gathered, gathered
    assert "attn/gather" not in text
    # 4.6 MB here; the two gathered rungs a layer made it 275 MB, and a
    # copy of a K leaf would be 604 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    for li in range(2):
        assert sum(f"layer{li}/attn/flash_decode/flash_fwd" in c
                   and c.lstrip().lstrip("%").startswith("flash_fwd")
                   for c in calls) == 1, li


# kanana-2-30b-a3b behind MLADecoder (PR 37): 32 heads over a latent of 512
# and 64 rotary lanes, 48 slots at an 18432-row rung packed two positions a
# row, prompts of 16384 expanded to keys of 192 and values of 128.
def _mla_decode(q_lat, q_rope, leaf, n):
    from deeplearning4j_tpu.kernels.mla_attention import \
        mla_attention_decode
    return mla_attention_decode(q_lat, q_rope, leaf, n, 192 ** -0.5,
                                impl="pallas", interpret=False)


def _mla_prefill(q, k, v):
    from deeplearning4j_tpu.kernels import flash_attention
    return flash_attention(q, k, v, causal=True, block_q=1024, block_k=1024,
                           native=True, interpret=False)


@pytest.mark.parametrize("fn,specs,name", [
    (_mla_decode, (((48, 32, 512), bf16), ((48, 32, 64), bf16),
                   ((48, 9216, 1152), bf16), ((48,), i32)), "mla_decode"),
    (_mla_prefill, (((1, 32, 16384, 192), bf16), ((1, 32, 16384, 192), bf16),
                    ((1, 32, 16384, 128), bf16)), "flash_fwd"),
], ids=["absorbed_decode_over_the_packed_leaf",
        "expanded_prefill_keys_192_values_128"])
def test_latent_attention_kernels_compile_for_v5e(compile_for_chip, fn,
                                                  specs, name):
    """The kernels `MLADecoder` adds, at the published widths: the absorbed
    decode over a `(48, 9216, 1152)` leaf in tiles of 512 packed rows under
    the slots' lengths, and `flash_attention` with values narrower than
    keys, bfloat16 operands as they come, in tiles of 1024."""
    text = compile_for_chip(fn, *specs)
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    assert len(calls) == 1 and name in calls[0]


def test_latent_decode_superstep_reads_the_leaf_in_place_for_v5e(
        compile_for_chip, one_chip):
    """`MLADecoder`'s superstep at the published widths (48 slots, a rung
    of 18432, the dense layer and one expert layer): one `mla_decode` a
    layer under the scope the benchmark reads, the expert layer's
    `grouped_mlp`, and no temporary the size of a latent leaf (1.02 GB):
    the row write is in place and the kernel takes the leaf as it lies."""
    import json

    from benchmarks.families.deepseek_v3_serve import model_config
    from deeplearning4j_tpu.generation.decode import MLADecoder
    from deeplearning4j_tpu.models import deepseek_v3

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmarks", "configs",
                           "kanana2_30b_a3b_ep8.json")) as f:
        cfg = model_config({**json.load(f), "num_hidden_layers": 2},
                           "bfloat16")
    slots, rung = 48, 18432
    params = jax.eval_shape(lambda k: deepseek_v3.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dec = MLADecoder(cfg, params, attn_impl="pallas")
    cache = jax.eval_shape(lambda: dec.init_cache(slots, rung))
    assert [l.shape for l in cache["kv"]] == [(48, 9216, 1152)] * 2

    compiled = _compile_one_step_superstep(dec, params, cache, slots,
                                           one_chip)
    text = compiled.as_text()
    # 20 MB at 8 layers; a copy of one leaf would be 1019 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    assert not [l for l in text.splitlines()
                if "bf16[48,9216,1152]" in l and " copy(" in l]
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    for li in range(2):
        assert sum(f"layer{li}/attn/flash_decode/mla_decode" in c
                   for c in calls) == 1, li
    assert sum("layer1/moe/experts/grouped_mlp" in c for c in calls) == 1
    assert not any("layer0/moe" in c for c in calls)


# Trinity-Mini behind AfmoeDecoder: 32 query heads over 4 KV heads
# of 128, sliding layers on a ring of 2048 rows beside full layers on the
# 18432-row rung, prompts of 16384 through the banded prefill kernel.
def _prefill_kernel_module(text):
    """The Mosaic module of the one kernel in a lowered program, printed
    without source locations."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    kernel, = re.findall(r'\\22body\\22: \\22([^\\]*)\\22', text)
    context = jax_mlir.make_ir_context()
    context.allow_unregistered_dialects = True
    with context:
        return ir.Module.parse(base64.b64decode(kernel)) \
            .operation.get_asm(enable_debug_info=False)


def test_prefill_kernel_without_a_window_is_the_kernel_it_was_for_v5e(
        one_chip):
    """`flash_attention(..., window=None)`, as BERT's and kanana-2's
    prefills call it, builds the Mosaic module it built before the band
    came in (sha256 of the text the commit before it printed): the causal
    kernel at BERT's serving shape, and the native one at kanana-2's keys
    of 192 and values of 128 in tiles of 1024."""
    import hashlib

    from deeplearning4j_tpu.kernels import flash_attention

    def lowered(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, bf16, sharding=one_chip)
                for s in shapes]
        with jax.default_matmul_precision("default"):
            return jax.jit(fn).lower(*args).as_text()

    bert = lowered(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False), *[(8, 12, 512, 64)] * 3)
    kanana = lowered(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=1024, block_k=1024, native=True,
        interpret=False), (1, 32, 16384, 192), (1, 32, 16384, 192),
        (1, 32, 16384, 128))
    got = [hashlib.sha256(_prefill_kernel_module(t).encode()).hexdigest()[
        :16] for t in (bert, kanana)]
    assert got == ["aa8fea3ae37bf56a", "5482702a70bc08fb"], got


def _band_prefill(q, k, v):
    from deeplearning4j_tpu.kernels import flash_attention
    return flash_attention(q, k, v, causal=True, block_q=1024, block_k=1024,
                           native=True, window=2048, interpret=False)


def test_banded_prefill_compiles_for_v5e(compile_for_chip):
    """The band at the cell's largest prompt: 32 heads of 128, 16384
    tokens, a window of 2048 in tiles of 1024 (three k tiles a q tile in
    the grid, where the causal call has sixteen)."""
    text = compile_for_chip(_band_prefill, *[((1, 32, 16384, 128), bf16)] * 3)
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    assert len(calls) == 1 and "flash_fwd" in calls[0]


@pytest.mark.parametrize("window,d,dv", [
    (None, 192, 128), (None, 128, 128), (2048, 128, 128), (None, 64, 64)],
    ids=["kanana2_keys_192_values_128", "trinity_full_layer",
         "trinity_band", "lfm2_attention_layer"])
def test_prefill_over_the_pairs_in_use_compiles_for_v5e(compile_for_chip,
                                                        window, d, dv):
    """`flash_attention(..., lengths=)` as the three long-context admits
    call it at their largest bucket: 32 heads, 16384 rows in tiles of
    1024, the grid (heads, step) over the pairs in use, its extent and
    its step table read from the prompt's length when the call runs."""
    from deeplearning4j_tpu.kernels import flash_attention

    def prefill(q, k, v, n):
        return flash_attention(q, k, v, causal=True, block_q=1024,
                               block_k=1024, native=True, window=window,
                               lengths=n, interpret=False)

    text = compile_for_chip(prefill, ((1, 32, 16384, d), bf16),
                            ((1, 32, 16384, d), bf16),
                            ((1, 32, 16384, dv), bf16), ((1,), i32))
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    assert len(calls) == 1 and "flash_fwd" in calls[0]


def test_window_decode_superstep_keeps_both_leaves_in_place_for_v5e(
        compile_for_chip, one_chip):
    """`AfmoeDecoder`'s superstep at the published widths (48 slots, a rung
    of 18432, the first 4 layers: two dense sliding layers, an expert
    sliding layer and an expert full layer): the rings `(48, 2048, 512)`
    and the full leaves `(48, 18432, 512)` are written and read in place,
    with no copy of either and no temporary the size of a leaf; one
    `flash_fwd` a layer under the scope of its kind (`window` or
    `global`), and the expert layers' `grouped_mlp`."""
    import json

    from benchmarks.families.afmoe_serve import model_config
    from deeplearning4j_tpu.generation.decode import AfmoeDecoder
    from deeplearning4j_tpu.models import afmoe

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmarks", "configs",
                           "trinity_mini_ep8.json")) as f:
        cfg = model_config({**json.load(f), "num_hidden_layers": 4},
                           "bfloat16")
    slots, rung = 48, 18432
    params = jax.eval_shape(lambda k: afmoe.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dec = AfmoeDecoder(cfg, params, attn_impl="pallas")
    cache = jax.eval_shape(lambda: dec.init_cache(slots, rung))
    assert [l.shape for l in cache["k"]] \
        == [(48, 2048, 512)] * 3 + [(48, 18432, 512)]

    compiled = _compile_one_step_superstep(dec, params, cache, slots,
                                           one_chip)
    text = compiled.as_text()
    # a copy of one full leaf would be 906 MB, of a ring 101 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    assert not [l for l in text.splitlines()
                if re.search(r"bf16\[48,(2048|18432),512\]", l)
                and " copy(" in l]
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    for li, kind in enumerate(("window", "window", "window", "global")):
        assert sum(f"layer{li}/attn/{kind}/flash_decode/flash_fwd" in c
                   for c in calls) == 1, li
    for li in (2, 3):
        assert sum(f"layer{li}/moe/experts/grouped_mlp" in c
                   for c in calls) == 1, li
    assert not any("layer0/moe" in c or "layer1/moe" in c for c in calls)


def test_shortconv_decode_superstep_keeps_leaves_and_tails_in_place_for_v5e(
        compile_for_chip, one_chip):
    """`Lfm2MoeDecoder`'s superstep at the published widths (48 slots, a
    rung of 18432, the first 4 layers: two dense conv layers, an expert
    attention layer and an expert conv layer): the K/V leaves `(48, 18432,
    512)` and the conv tails `(48, 2, 2048)` are written and read in place,
    with no copy of either and no temporary the size of a leaf; the tied
    head reads the embedding as it lies (no transposed copy of its `(65536,
    2048)`); one `flash_fwd` under the scope the benchmark reads, and the
    expert layers' `grouped_mlp` (all 64 experts held, none shared)."""
    import json

    from benchmarks.families.lfm2_moe_serve import model_config
    from deeplearning4j_tpu.generation.decode import Lfm2MoeDecoder
    from deeplearning4j_tpu.models import lfm2_moe

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmarks", "configs",
                           "lfm2_24b_a2b_pp5.json")) as f:
        cfg = model_config({**json.load(f), "num_hidden_layers": 4},
                           "bfloat16")
    slots, rung = 48, 18432
    params = jax.eval_shape(lambda k: lfm2_moe.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dec = Lfm2MoeDecoder(cfg, params, attn_impl="pallas")
    cache = jax.eval_shape(lambda: dec.init_cache(slots, rung))
    assert [l.shape for l in cache["k"]] == [(48, 18432, 512)]
    assert [l.shape for l in cache["conv"]] == [(48, 2, 2048)] * 3

    compiled = _compile_one_step_superstep(dec, params, cache, slots,
                                           one_chip)
    text = compiled.as_text()
    # a copy of one K leaf would be 906 MB, of the embedding 268 MB; a
    # copy into on-chip memory (`S(1)`, a 393 KB tail among the small
    # operands the compiler prefetches) re-lays nothing in HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    assert not [l for l in text.splitlines()
                if re.search(r"bf16\[(48,18432,512|48,2,2048|65536,2048|"
                             r"2048,65536)\]", l)
                and (" copy(" in l and "S(1)} copy(" not in l
                     or " transpose(" in l)]
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    assert sum("layer2/attn/flash_decode/flash_fwd" in c
               for c in calls) == 1
    for li in (2, 3):
        assert sum(f"layer{li}/moe/experts/grouped_mlp" in c
                   for c in calls) == 1, li
    assert not any("layer0/moe" in c or "layer1/moe" in c
                   or "moe/shared" in c for c in calls)


def _published(config, **overrides):
    """(the family's decoder over shapes, the parameters' shapes) of a
    serving cell's configuration file, with `overrides` of its keys."""
    import importlib
    import json

    from deeplearning4j_tpu.generation import decode
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmarks", "configs",
                           config + ".json")) as f:
        file = {**json.load(f), **overrides}
    family = importlib.import_module(f"benchmarks.families.{file['family']}")
    module, decoder = {
        "lfm2_moe_serve": ("lfm2_moe", "Lfm2MoeDecoder"),
        "afmoe_serve": ("afmoe", "AfmoeDecoder"),
        "deepseek_v3_serve": ("deepseek_v3", "MLADecoder"),
        "keye_vl_serve": ("keye_vl", "KeyeDecoder"),
        "nemotron_h_serve": ("nemotron_h", "NemotronHDecoder"),
    }[file["family"]]
    model = importlib.import_module(f"deeplearning4j_tpu.models.{module}")
    cfg = family.model_config(file, "bfloat16")
    params = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return getattr(decode, decoder)(cfg, params, attn_impl="pallas"), params


def _lower_admit(dec, params, bucket, rung, slots, one_chip):
    """`dec.prefill` over a prompt bucket, the cache donated, lowered for
    the described chip with `jax.default_backend()` steered to "tpu"."""
    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=one_chip), tree)
    cache = jax.eval_shape(lambda: dec.init_cache(slots, rung))
    scalar = jax.ShapeDtypeStruct((), i32, sharding=one_chip)
    args = (on_chip(params), on_chip(cache), scalar,
            jax.ShapeDtypeStruct((bucket,), i32, sharding=one_chip), scalar)
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        with jax.default_matmul_precision("default"):
            return jax.jit(lambda p, c, s, prompt, plen: dec.prefill(
                (p,), c, s, prompt, plen), donate_argnums=(1,)).lower(*args)
    finally:
        jax.default_backend = real


def test_an_lfm2_admit_of_16384_reads_its_experts_four_times_for_v5e(
        compile_for_chip, one_chip):
    """`Lfm2MoeDecoder.prefill` at the cell's largest bucket (16384
    tokens, the first 3 layers: two dense conv layers and an expert
    attention layer) compiles for the described chip with the expert
    layer in 4 calls of the kernel, 4096 tokens each beside the 64 gated
    experts' blocks: 102 MiB of scoped VMEM a call, which Mosaic takes."""
    dec, params = _published("lfm2_24b_a2b_pp5", num_hidden_layers=3)
    compiled = _lower_admit(dec, params, 16384, 18432, 48,
                            one_chip).compile()
    calls = [l for l in compiled.as_text().splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    assert sum("layer2/moe/experts/grouped_mlp" in c for c in calls) == 4
    assert sum("grouped_mlp" in c for c in calls) == 4
    # 0.92 GB at 4 layers (1.04 in runs of 2048 under `lax.map`)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def _program_digest(text):
    """sha256 of a lowered program: its StableHLO text outside the
    kernels' bodies, then each kernel's Mosaic module printed without
    source locations."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    body = r'\\22body\\22: \\22([^\\]*)\\22'
    context = jax_mlir.make_ir_context()
    context.allow_unregistered_dialects = True
    parts = [re.sub(body, "", text)]
    with context:
        parts += [ir.Module.parse(base64.b64decode(kernel))
                  .operation.get_asm(enable_debug_info=False)
                  for kernel in re.findall(body, text)]
    return hashlib.sha256("\n".join(parts).encode()).hexdigest()[:16]


#: program -> (configuration, its keys overridden, slots, rung, bucket of
#: an admit or None for the superstep, the `_program_digest` that the
#: commit before a call's rows took what its weight blocks leave printed):
#: every expert family's decode step at the published widths, and Keye's
#: admit, whose own runs of 2048 tokens each fit one call of the kernel
AS_BEFORE = {
    "lfm2_superstep": ("lfm2_24b_a2b_pp5", dict(num_hidden_layers=4), 48,
                       18432, None, "6a179c89ca4a65db"),
    "trinity_superstep": ("trinity_mini_ep8", dict(num_hidden_layers=4),
                          48, 18432, None, "fa2860f17dbeca2b"),
    "kanana2_superstep": ("kanana2_30b_a3b_ep8", dict(num_hidden_layers=2),
                          48, 18432, None, "7954d3105e756b98"),
    "keye_superstep": ("keye_vl2_30b_a3b_ep8", dict(num_hidden_layers=2),
                       32, 18432, None, "a9e9048f8071560a"),
    "hybrid_superstep": ("nemotron3_super_120b_ep4", dict(
        num_hidden_layers=3, held={"pattern": "*EM", "experts": [0, 128]}),
        128, 1024, None, "b16c28b9579ce943"),
    "keye_admit": ("keye_vl2_30b_a3b_ep8", dict(num_hidden_layers=1), 32,
                   18432, 8192, "ad731055150ed821"),
}


@pytest.mark.parametrize("program", list(AS_BEFORE))
def test_what_fit_one_call_lowers_as_it_did_for_v5e(one_chip, program):
    """A call of the expert kernel whose tokens fit what a call held
    before its rows' VMEM became what its weight blocks leave is the call
    it was: every expert family's superstep and Keye's admit lower, for
    the described chip, to the program the commit before printed
    (`_program_digest`; a PR that changes one of them on purpose prints
    the new digest from here)."""
    config, overrides, slots, rung, bucket, want = AS_BEFORE[program]
    dec, params = _published(config, **overrides)
    if bucket is None:
        cache = jax.eval_shape(lambda: dec.init_cache(slots, rung))
        lowered = _lower_one_step_superstep(dec, params, cache, slots,
                                            one_chip)
    else:
        lowered = _lower_admit(dec, params, bucket, rung, slots, one_chip)
    text = lowered.as_text()
    assert "grouped_mlp" in text
    got = _program_digest(text)
    assert got == want, got
