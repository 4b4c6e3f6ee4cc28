"""The Nemotron-H hybrid (models/nemotron_h.py, NemotronHDecoder, the
share-holding expert layer, the grouped-query decode kernel) against the
plain reference that the benchmark keeps
(benchmarks/families/nemotron_h_serve.py: sequential scan, looped experts).

Toy widths that keep every ratio of the published model: hidden 64, 4
query heads over 2 KV heads of 16, 8 Mamba heads of 8 in 2 groups, state
16, chunk 8, 16 experts with 4 a token in a latent of 32, pattern `*EMEM`.
Float32 on the CPU at the highest matmul precision (conftest), so the
tolerances below are summation-order noise, not a precision."""
import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from benchmarks.families import nemotron_h_serve as family
from deeplearning4j_tpu.generation.decode import NemotronHDecoder
from deeplearning4j_tpu.generation.server import GenerationServer
from deeplearning4j_tpu.models import nemotron_h as nh
from deeplearning4j_tpu.parallel.moe import routed_experts

# (the package exports a function of the module's name)
fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

TOY = dict(
    vocab_size=96, hidden_size=64, hybrid_override_pattern="*EMEM",
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, n_routed_experts=16, num_experts_per_tok=4,
    moe_latent_size=32, moe_intermediate_size=84,
    moe_shared_expert_intermediate_size=84, routed_scaling_factor=5,
    norm_eps=1e-5, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, num_hidden_layers=5,
    held={"pattern": "*EMEM", "experts": [0, 16]})
#: float32 both sides, different summation orders (chunked against
#: sequential, grouped against looped): logits of size 1 agree to 1e-6
LOGIT_TOL = 2e-5


@pytest.fixture(scope="module")
def toy():
    cfg = nh.NemotronHConfig.from_dict(TOY)
    return cfg, nh.init_params(cfg, jax.random.PRNGKey(7))


def _reference(params, ids):
    return family.reference_logits(params, jnp.asarray(ids),
                                   family.reference_sizes(TOY))


def _ids(seed, *shape):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"], shape).astype(np.int32)


# -- the full forward ---------------------------------------------------------
@pytest.mark.parametrize("t", [8, 21], ids=["on_chunk", "off_chunk"])
def test_forward_matches_reference(toy, t):
    cfg, params = toy
    ids = _ids(t, 2, t)
    got = jax.jit(lambda p, x: nh.forward(cfg, p, x))(params, ids)
    np.testing.assert_allclose(got, _reference(params, ids),
                               atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("name,layer,centred", [
    ("out_proj", 2, True), ("w2", 1, True), ("shared_w2", 1, True),
    ("w1", 1, False), ("in_proj", 2, False)])
def test_projections_after_a_one_signed_activation_are_centred(
        toy, name, layer, centred):
    """`init_params` takes each output column's mean over the input rows
    off the matrices that read ReLU² or the gated scan output, so that no
    seed adds one vector to every token; the others stay as drawn (a
    column mean of 84 or 64 draws of normal 0.02 is some 2e-3)."""
    w = np.asarray(toy[1]["layers"][layer][name], np.float64)
    mean = np.abs(w.mean(-2)).max()
    assert (mean < 1e-8) if centred else (mean > 1e-3)


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(ValueError, match="pattern"):
        nh.NemotronHConfig.from_dict(TOY, pattern="ME-")
    with pytest.raises(ValueError, match="experts_held"):
        nh.NemotronHConfig.from_dict(TOY, experts_held=(12, 8))


# -- the chunked scan ---------------------------------------------------------
def _sequential_scan(x, dt, a, b, c):
    """The recurrence as written: H_t = exp(dt_t a) H_{t-1} + dt_t x_t (x)
    B_t, y_t = H_t C_t; numpy, float64."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    bsz, t, nhd, hd = x.shape
    r = nhd // b.shape[2]
    b, c = np.repeat(b, r, axis=2), np.repeat(c, r, axis=2)
    h = np.zeros((bsz, nhd, hd, b.shape[-1]))
    ys = []
    for i in range(t):
        h = np.exp(dt[:, i] * a)[..., None, None] * h \
            + (dt[:, i, :, None] * x[:, i])[..., None] * b[:, i, :, None, :]
        ys.append((h * c[:, i, :, None, :]).sum(-1))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("t", [8, 16, 5, 21],
                         ids=["one_chunk", "two_chunks", "short", "ragged"])
def test_chunked_scan_matches_sequential(t):
    rng = np.random.default_rng(t)
    x = rng.normal(size=(2, t, 8, 8)).astype(np.float32)
    dt = rng.uniform(0.001, 0.5, (2, t, 8)).astype(np.float32)
    a = -rng.uniform(1, 16, 8).astype(np.float32)
    b, c = (rng.normal(size=(2, t, 2, 16)).astype(np.float32)
            for _ in range(2))
    y, state = nh.ssd_chunked(*map(jnp.asarray, (x, dt, a, b, c)), 8)
    want_y, want_state = _sequential_scan(x, dt, a, b, c)
    # float32 products against float64: 1e-6 of values of size 1-10
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=1e-5)


def test_padded_bucket_leaves_the_state_of_the_last_real_token(toy):
    """A prompt of 11 in a bucket of 16: dt = 0 past plen holds the state,
    and the tail is the three rows before plen."""
    cfg, params = toy
    layer = params["layers"][2]
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 64))
    plen = jnp.array([11])
    out_pad, (state_pad, tail_pad) = nh.mamba_mixer(cfg, layer, u, plen)
    out, (state, tail) = nh.mamba_mixer(cfg, layer, u[:, :11], plen)
    np.testing.assert_allclose(out_pad[:, :11], out, atol=1e-6)
    np.testing.assert_allclose(state_pad, state, atol=1e-6)
    np.testing.assert_array_equal(tail_pad, tail)
    xbc = (u @ layer["in_proj"])[0, :, cfg.d_inner:cfg.d_inner
                                 + cfg.conv_dim]
    np.testing.assert_allclose(tail[0], xbc[8:11], atol=1e-6)


# -- the decoder: prefill, then decode through both kinds of state ----------
@pytest.mark.parametrize("attn_impl", ["dense", "pallas"])
def test_prefill_then_twelve_steps_match_the_full_forward(toy, attn_impl):
    """Slot 1 of a 2-slot cache takes a prompt of 9 in a bucket of 16 and
    decodes 12 greedy tokens while slot 0 runs another sequence: every
    step's logits are the reference's full forward at that position."""
    cfg, params = toy
    dec = NemotronHDecoder(cfg, params, attn_impl=attn_impl)
    margs = dec.model_args()
    prefill = jax.jit(dec.prefill)
    step = jax.jit(dec.step)
    prompts = [_ids(1, 5), _ids(2, 9)]
    cache = dec.init_cache(2, 32)
    seqs, tokens, got = [], [], [[], []]
    for slot, prompt in enumerate(prompts):
        padded = np.zeros(16, np.int32)
        padded[:len(prompt)] = prompt
        cache, logits = prefill(margs, cache, np.int32(slot), padded,
                                np.int32(len(prompt)))
        got[slot].append(logits)
        tokens.append(int(np.argmax(logits)))
        seqs.append(list(prompt))
    pos = np.array([len(p) for p in prompts], np.int32)
    for _ in range(12):
        logits, cache = step(margs, cache, np.array(tokens, np.int32), pos)
        for slot in range(2):
            seqs[slot].append(tokens[slot])
            got[slot].append(logits[slot])
            tokens[slot] = int(np.argmax(logits[slot]))
        pos = pos + 1
    for slot, prompt in enumerate(prompts):
        # causal: one full forward gives every step's reference
        ref = _reference(params, np.array(seqs[slot])[None])[0]
        np.testing.assert_allclose(np.stack(got[slot]),
                                   ref[len(prompt) - 1:], atol=LOGIT_TOL,
                                   rtol=0)
    # 12 steps x 2 expert layers x 2 tokens x 4 choices, every expert held
    assert cache["counts"][0] == 12 * 2 * 2 * 4
    assert 0 < cache["counts"][2] <= cache["counts"][0]


def test_server_serves_the_decoder_and_reports_its_counters(toy):
    cfg, params = toy
    srv = GenerationServer(NemotronHDecoder(cfg, params), slots=2,
                           cache_lengths=[16, 32], prompt_buckets=[8, 16],
                           method="greedy", max_new_tokens=8, seed=0)
    try:
        prompt = _ids(4, 6)
        toks = srv.generate(prompt, max_new_tokens=8, timeout=120)
        long = srv.generate(_ids(5, 12), max_new_tokens=16, timeout=120)
        assert srv._rung == 32 and len(long) == 16     # grew mid-service
        st = srv.status()
    finally:
        srv.shutdown()
    # greedy: each served token is the reference's argmax after its prefix
    ref = _reference(params, np.array(list(prompt) + toks[:-1])[None])[0]
    ref = ref[len(prompt) - 1:]
    assert (ref[np.arange(8), toks] >= ref.max(-1) - LOGIT_TOL).all()
    assert st["decoder"] == "NemotronHDecoder"
    # every step routes both slots' tokens: 2 expert layers x 2 x 4 pairs
    assert st["moe_pairs"] == 16 * st["steps"] > 0
    assert 0 < st["moe_expert_reads"] <= 2 * 16 * st["steps"]
    assert st["moe_pairs_max"] >= 2 * st["steps"]


def test_grow_pads_kv_leaves_and_keeps_state_leaves(toy):
    cfg, params = toy
    dec = NemotronHDecoder(cfg, params)
    cache = jax.tree_util.tree_map(
        lambda l: jnp.arange(l.size, dtype=jnp.float32).reshape(
            l.shape).astype(l.dtype), dec.init_cache(2, 8))
    grown = dec.grow(cache, 24)
    for name in ("k", "v"):
        for old, new in zip(cache[name], grown[name]):
            assert new.shape == (2, 24, cfg.kv_width)
            np.testing.assert_array_equal(new[:, :8], old)
            assert not np.asarray(new[:, 8:]).any()
    for name in ("ssm", "conv"):
        assert len(grown[name]) == 2
        for old, new in zip(cache[name], grown[name]):
            assert new is old
    assert grown["counts"] is cache["counts"]
    assert dec.uses_cache_rungs and not dec.supports_draft


# -- the expert layer that holds a share ------------------------------------
def _moe_inputs(toy):
    cfg, params = toy
    layer = params["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(11), (24, 64))
    scores = jax.nn.sigmoid(u @ layer["router"])
    return cfg, layer, u, scores, u @ layer["down"]


def _share(toy, first, count):
    cfg, layer, _, scores, lat = _moe_inputs(toy)
    return routed_experts(
        lat, scores, layer["e_bias"], layer["w1"][first:first + count],
        layer["w2"][first:first + count], (first, count),
        cfg.num_experts_per_tok, cfg.routed_scaling_factor, nh.relu2)


@pytest.mark.parametrize("first", [0, 4, 8, 12])
def test_share_is_its_experts_part_of_the_layer(toy, first):
    """A share of 4 experts against the loop over those 4 under a mask."""
    cfg, layer, _, scores, lat = _moe_inputs(toy)
    out, counts = _share(toy, first, 4)
    _, idx = jax.lax.top_k(scores + layer["e_bias"], 4)
    chosen = jnp.take_along_axis(scores, idx, -1)
    wts = 5.0 * chosen / chosen.sum(-1, keepdims=True)
    want = jnp.zeros_like(lat)
    sizes = []
    for e in range(first, first + 4):
        w_tok = jnp.where(idx == e, wts, 0.0).sum(-1)
        want = want + w_tok[:, None] * (
            nh.relu2(lat @ layer["w1"][e]) @ layer["w2"][e])
        sizes.append(int((idx == e).sum()))
    np.testing.assert_allclose(out, want, atol=1e-6)
    assert list(counts) == [sum(sizes), sum(s > 0 for s in sizes),
                            max(sizes)]


def test_four_shares_add_up_to_the_uncut_layer(toy):
    """Four chips of 4 experts each: the routed parts add up, and with the
    latent projections and the shared expert counted once the sum is the
    uncut layer (and the reference's)."""
    cfg, layer, u, _, _ = _moe_inputs(toy)
    parts = [_share(toy, first, 4) for first in (0, 4, 8, 12)]
    whole, whole_counts = _share(toy, 0, 16)
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, atol=1e-6)
    assert sum(int(p[1][0]) for p in parts) == int(whole_counts[0]) \
        == 24 * 4
    uncut, _ = nh.moe_mixer(cfg, layer, u)
    routed_up = sum(p[0] for p in parts) @ layer["up"]
    shared = nh.relu2(u @ layer["shared_w1"]) @ layer["shared_w2"]
    np.testing.assert_allclose(routed_up + shared, uncut, atol=1e-5)


# -- the expert layer through its Pallas kernel (interpret mode) ------------
def _looped_experts(x, scores, bias, w_in, w_out, first, count, k, scale):
    """The reference's way: every held expert over every token, masked."""
    _, idx = jax.lax.top_k(scores + bias, k)
    chosen = jnp.take_along_axis(scores, idx, -1)
    wts = scale * chosen / chosen.sum(-1, keepdims=True)
    out, sizes = jnp.zeros((x.shape[0], w_out.shape[2])), []
    for j in range(count):
        w_tok = jnp.where(idx == first + j, wts, 0.0).sum(-1)
        out = out + w_tok[:, None] * (nh.relu2(x @ w_in[j]) @ w_out[j])
        sizes.append(int((idx == first + j).sum()))
    return out, [sum(sizes), sum(s > 0 for s in sizes), max(sizes)]


@pytest.mark.parametrize("t,first,count", [
    (24, 0, 16), (24, 4, 4), (24, 12, 4), (1, 0, 4), (1, 8, 8), (80, 4, 8)],
    ids=["all_held", "a_quarter", "the_last_quarter", "one_token",
         "one_token_half", "groups_over_a_tile"])
def test_kernel_path_equals_ragged_path_and_looped_experts(t, first, count):
    """`routed_experts` at widths the kernel takes (latent 128, expert
    width 256; 16 experts, 4 a token): the Pallas path, interpreted, the
    `lax.ragged_dot` path and the loop over experts agree, counts and
    all. With a quarter held, three quarters of the pair buffer belong to
    no group."""
    rng = np.random.default_rng(t + first)
    x = jnp.asarray(rng.normal(size=(t, 128)), jnp.float32)
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(t, 16)),
                                        jnp.float32))
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.01, jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(count, 128, 256)) * 0.05,
                       jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(count, 256, 128)) * 0.05,
                        jnp.float32)
    args = (x, scores, bias, w_in, w_out, (first, count), 4, 5.0, nh.relu2)
    got, got_counts = routed_experts(*args, impl="pallas", interpret=True)
    ragged, ragged_counts = routed_experts(*args, impl="ragged")
    auto, _ = routed_experts(*args)          # the CPU takes the plain path
    want, want_counts = _looped_experts(x, scores, bias, w_in, w_out, first,
                                        count, 4, 5.0)
    np.testing.assert_array_equal(auto, ragged)
    np.testing.assert_allclose(got, ragged, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert list(got_counts) == list(ragged_counts) == want_counts


def test_rows_the_kernel_never_visits_do_not_reach_the_output(monkeypatch):
    """The kernel neither picks nor adds a tiled row that holds no pair (a
    group's last tile past its last pair, the tiles past the last group):
    on the chip such a row holds whatever the buffer held. Here the scratch
    starts as NaN, and every such row names a pair of weight NaN; they
    change nothing."""
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.kernels import grouped_matmul as gm
    real = gm._fused_mlp

    def poisoned(x, w_in, w_out, tile_group, tile_count, tiles, tile_pair,
                 rows, weights, activation, tm, *rest):
        row = jnp.arange(tile_pair.shape[0])
        used = row % tm < tile_count[row // tm]
        m = rows.shape[0]
        return real(x, w_in, w_out, tile_group, tile_count, tiles,
                    jnp.where(used, tile_pair, m), jnp.append(rows, 0),
                    jnp.append(weights, jnp.nan), activation, tm, *rest)

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(24, 128)), jnp.float32)
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(24, 16)),
                                        jnp.float32))
    w_in = jnp.asarray(rng.normal(size=(4, 128, 256)) * 0.05, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(4, 256, 128)) * 0.05, jnp.float32)
    args = (x, scores, jnp.zeros(16), w_in, w_out, (4, 4), 4, 5.0, nh.relu2)
    clean, _ = routed_experts(*args, impl="pallas", interpret=True)
    monkeypatch.setattr(gm, "_fused_mlp", poisoned)
    got, counts = routed_experts(*args, impl="pallas",
                                 interpret=pltpu.InterpretParams())
    assert int(counts[0]) < 24 * 4 // 2        # most pairs are held elsewhere
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, clean)


def test_unknown_expert_impl_is_named():
    z = jnp.zeros
    with pytest.raises(ValueError, match="unknown routed_experts impl"):
        routed_experts(z((2, 8)), z((2, 4)), z(4), z((4, 8, 8)),
                       z((4, 8, 8)), (0, 4), 2, impl="megablox")


# -- the grouped-query decode kernel (interpret mode) -----------------------
@pytest.mark.parametrize("hq,hkv,d,c,block_k", [
    (4, 2, 16, 24, 8),        # the toy model's heads, three k tiles
    (32, 2, 128, 256, 128),   # the published heads
    (8, 1, 32, 40, 512),      # multi-query: one KV head, one whole tile
])
def test_grouped_query_kernel_matches_masked_attend(hq, hkv, d, c, block_k):
    rng = np.random.default_rng(hq * c)
    q = jnp.asarray(rng.normal(size=(3, hq, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(3, c, hkv * d)), jnp.float32)
            for _ in range(2))
    mask = jnp.arange(c)[None, :] < jnp.array([c, 1, 0])[:, None]
    want = fa._masked_attend(q[:, :, None], k, v, mask[:, None, :])[:, :, 0]
    got = fa.flash_attention_decode(q, k, v, mask, impl="pallas",
                                    block_k=block_k, interpret=True)
    # float32, online softmax against one softmax
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert not np.asarray(got[2]).any()          # no valid row: zeros
    # query head i reads KV head i // group: against a per-head softmax
    i = hq - 1
    kv = i // (hq // hkv)
    s = (k[0, :, kv * d:(kv + 1) * d] @ q[0, i]) / np.sqrt(d)
    p = jax.nn.softmax(s)
    np.testing.assert_allclose(got[0, i], p @ v[0, :, kv * d:(kv + 1) * d],
                               atol=2e-6)


def _tree_decode_kernel(q_ref, k_ref, v_ref, km_ref, o_ref, acc_ref, l_ref,
                        m_ref, *, scale, head_dim):
    """The decode kernel as the tree had it before grouped-query heads
    (commit d601c83), kept here as the yardstick for `Hq = Hkv`."""
    kj = pl.program_id(1)
    hp, hd = acc_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 1)
    own = (lane >= row * head_dim) & (lane < (row + 1) * head_dim)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)

    q = jnp.where(own, q_ref[0].astype(jnp.float32) * scale, 0.0)
    s = jax.lax.dot_general(
        q, k_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    s = jnp.where(km_ref[0] > 0, s, -1e30)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kj == pl.num_programs(1) - 1)
    def _finalize():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = jnp.sum(jnp.where(own, o, 0.0), axis=0,
                           keepdims=True).astype(o_ref.dtype)


def _tree_decode(q, k_cache, v_cache, cache_mask, block_k):
    b, h, d = q.shape
    c, hd = k_cache.shape[1], k_cache.shape[2]
    hp = -(-h // 8) * 8
    cache_spec = pl.BlockSpec((1, block_k, hd), lambda i, j: (i, j, 0))
    row_spec = pl.BlockSpec((1, 1, hd), lambda i, j: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_tree_decode_kernel, scale=1.0 / (d ** 0.5),
                          head_dim=d),
        grid=(b, c // block_k),
        in_specs=[row_spec, cache_spec, cache_spec,
                  pl.BlockSpec((1, 1, block_k), lambda i, j: (i, 0, j))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((hp, hd), jnp.float32),
                        pltpu.VMEM((hp, 1), jnp.float32),
                        pltpu.VMEM((hp, 1), jnp.float32)],
        interpret=True,
    )(q.reshape(b, 1, hd), k_cache, v_cache,
      cache_mask.astype(jnp.int32)[:, None, :])
    return out.reshape(b, h, d)


@pytest.mark.parametrize("h,d,c,block_k", [(12, 64, 64, 32), (3, 8, 16, 16)],
                         ids=["bert_base_heads", "tiny"])
def test_equal_heads_kernel_is_bit_equal_to_the_trees(h, d, c, block_k):
    rng = np.random.default_rng(h)
    q = jnp.asarray(rng.normal(size=(2, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, c, h * d)), jnp.float32)
            for _ in range(2))
    mask = jnp.arange(c)[None, :] < jnp.array([c - 3, 5])[:, None]
    got = fa.flash_attention_decode(q, k, v, mask, impl="pallas",
                                    block_k=block_k, interpret=True)
    np.testing.assert_array_equal(got, _tree_decode(q, k, v, mask, block_k))
    # and the einsum path moves nothing for equal heads
    dense = fa.flash_attention_decode(q, k, v, mask, impl="dense")
    np.testing.assert_allclose(got, dense, atol=2e-6)


def test_cache_operand_check_names_the_group_rule():
    z = jnp.zeros
    with pytest.raises(ValueError, match="KV heads dividing the 3 query"):
        fa.flash_attention_decode(z((2, 3, 8)), z((2, 4, 16)),
                                  z((2, 4, 16)), z((2, 4)))
    out = fa.flash_attention_decode(z((2, 4, 8)), z((2, 4, 16)),
                                    z((2, 4, 16)), jnp.ones((2, 4)))
    assert out.shape == (2, 4, 8)
