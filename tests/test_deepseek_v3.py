"""The DeepSeek-V3 style language model (models/deepseek_v3.py, MLADecoder,
the latent decode kernel of kernels/mla_attention.py, `flash_attention` with
values narrower than keys) against the plain reference that the benchmark
keeps (benchmarks/families/deepseek_v3_serve.py: expanded-form attention
under a plain causal mask, rotary on reshaped pairs, the router's choice by
a sort, looped experts).

Toy widths that keep the published model's ratios: hidden 64, 4 heads of 16
+ 8 (nope + rope) over a latent of 32, values of 16, a leading dense layer
of 96 and two expert layers of 16 experts of width 48, 4 a token, 2 shared.
Float32 on the CPU at the highest matmul precision (conftest), so the
tolerances below are summation-order noise, not a precision. The fixture's
weights are drawn LARGER than `init_params` draws them (x 8 into the latent
and its up-projections, x 2 into q and x 7.5 into `o` on top of the draw's
own 4 and 4, x 4 and x 10 into the feed-forwards, x 10 into the router), so
that what a position attends and
where it is routed decide its logits: with the seeded draw the residual
stream is mostly the token's own embedding and a wrong cache row would move
the logits by 1e-4."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import deepseek_v3_serve as family
from deeplearning4j_tpu.generation.decode import MLADecoder
from deeplearning4j_tpu.generation.server import GenerationServer
from deeplearning4j_tpu.kernels import mla_attention as mla
from deeplearning4j_tpu.models import deepseek_v3 as ds
from deeplearning4j_tpu.parallel.moe import routed_experts

fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

TOY = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=None,
    intermediate_size=96, moe_intermediate_size=48, n_routed_experts=16,
    num_experts_per_tok=4, n_shared_experts=2, first_k_dense_replace=1,
    routed_scaling_factor=2.448, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, norm_topk_prob=True,
    rope_interleave=True, rope_scaling=None, rope_theta=1e6,
    rms_norm_eps=1e-6, held={"experts": [0, 16]})
#: float32 both sides, different summation orders (an online softmax in
#: tiles against whole rows, absorbed against expanded products, grouped
#: against looped experts): logits of size 3 agree to 2e-6. A lower
#: precision fails it by orders (`test_lower_precision_...`)
LOGIT_TOL = 2e-5


def _sharpened(params):
    """Attention that decides the logits (the module's docstring)."""
    gains = dict(q=8.0 / ds.SEEDED_Q_GAIN, kva=8.0, k_up=8.0, v_up=8.0,
                 o=30.0 / ds.SEEDED_O_GAIN, router=10.0)
    for name in ("", "w_", "s_"):
        gains.update({name + "gate": 4.0, name + "up": 4.0,
                      name + "down": 10.0})
    return {**params, "layers": [
        {k: v * gains.get(k, 1.0) for k, v in layer.items()}
        for layer in params["layers"]]}


@pytest.fixture(scope="module")
def toy():
    cfg = ds.DeepseekV3Config.from_dict(TOY)
    return cfg, _sharpened(ds.init_params(cfg, jax.random.PRNGKey(7)))


def _reference(params, ids, sizes=None, **kw):
    return family.reference_logits(
        params, jnp.atleast_2d(jnp.asarray(ids)),
        sizes or family.reference_sizes(TOY), **kw)


def _ids(seed, *shape):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"], shape).astype(np.int32)


# -- the full forward ---------------------------------------------------------
@pytest.mark.parametrize("impl,t", [("dense", 40), ("dense", 57),
                                    ("pallas", 40), ("pallas", 72)],
                         ids=["dense", "dense_odd", "kernel",
                              "kernel_longer"])
def test_forward_matches_reference(toy, impl, t, monkeypatch):
    """The program's expanded form (its own rotation of neighbouring lanes,
    `flash_attention` at key width 24 and value width 16 in tiles of 16)
    against the reference's."""
    cfg, params = toy
    monkeypatch.setattr(ds, "PREFILL_BLOCK", 16)
    ids = _ids(t, 2, t)
    got = jax.jit(lambda p, x: ds.forward(cfg, p, x, impl=impl))(params, ids)
    np.testing.assert_allclose(got, _reference(params, ids), atol=LOGIT_TOL,
                               rtol=0)


@pytest.mark.parametrize("fault", family.FAULTS)
def test_planted_faults_move_the_reference(toy, fault):
    """The reference's planted faults (the controls of the cell's `check()`
    on the chip: `benchmarks/tests/test_rehearsal_latent.py`) are not the
    reference: each moves every late position's logits."""
    _, params = toy
    ids = _ids(5, 40)
    moved = np.abs(_reference(params, ids, fault=fault)
                   - _reference(params, ids)).max(-1)[0]
    assert float(moved[8:].min()) > 100 * LOGIT_TOL


def test_attention_decides_these_logits(toy):
    """What the comparisons here are worth: a position that attends the
    wrong rows (the sequence's first token changed) has other logits at
    EVERY later position."""
    cfg, params = toy
    ids = _ids(3, 40)
    other = ids.copy()
    other[0] = (ids[0] + 1) % TOY["vocab_size"]
    moved = jnp.abs(ds.forward(cfg, params, ids)
                    - ds.forward(cfg, params, other)).max(-1)
    assert float(moved[8:].min()) > 100 * LOGIT_TOL


def test_lower_precision_reference_fails_the_tolerance(toy):
    """What `LOGIT_TOL` is worth: the reference one precision down (float8
    weights, activations and latent rows) misses it by three orders."""
    _, params = toy
    ids = _ids(5, 1, 40)
    lower = _reference(params, ids, lower=True)
    assert float(jnp.abs(lower - _reference(params, ids)).max()) > 1e-2


def test_layer_zero_is_dense_and_layer_one_is_not(toy):
    cfg, params = toy
    assert cfg.is_dense(0) and not cfg.is_dense(1)
    assert {"gate", "up", "down"} <= set(params["layers"][0])
    assert "router" not in params["layers"][0]
    for layer in params["layers"][1:]:
        assert {"router", "router_bias", "w_gate", "s_gate"} <= set(layer)
        assert "gate" not in layer
        assert layer["w_gate"].shape == (16, 64, 48)
        assert layer["s_up"].shape == (64, 2 * 48)        # ONE SwiGLU
    # and both kinds are computed: each moves the logits
    ids = _ids(2, 24)
    base = ds.forward(cfg, params, ids)
    for li, name in ((0, "down"), (1, "s_down"), (2, "w_down")):
        layers = list(params["layers"])
        layers[li] = {**layers[li], name: layers[li][name] * 0}
        assert float(jnp.abs(ds.forward(
            cfg, {**params, "layers": layers}, ids) - base).max()) > 1e-3


def test_config_refuses_what_it_cannot_run():
    for key, value in (("q_lora_rank", 1536), ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn", "factor": 40}),
                       ("n_group", 8), ("rope_interleave", False),
                       ("norm_topk_prob", False), ("moe_layer_freq", 2)):
        with pytest.raises(ValueError, match=key):
            ds.DeepseekV3Config.from_dict({**TOY, key: value})
    with pytest.raises(ValueError, match="experts_held"):
        ds.DeepseekV3Config.from_dict(TOY, experts_held=(8, 16))
    cfg = ds.DeepseekV3Config.from_dict(TOY, experts_held=(4, 4))
    assert (cfg.qk_head_dim, cfg.latent_width) == (24, 40)
    assert cfg.attn_scale == 24 ** -0.5


# -- absorbed against expanded -----------------------------------------------
def test_absorbed_step_equals_expanded_form(toy):
    """The two forms of one layer's attention, float32: every position t of
    a sequence as a slot of its own, its absorbed query against the packed
    latent rows 0..t, against row t of the expanded causal attention. 1e-5:
    it is the same sum reordered (`q_nope . (c W_UK)` as `(q_nope W_UK^T) .
    c`, the value up-projection after the weighted sum instead of before),
    at outputs of size 2."""
    cfg, params = toy
    t = 48
    layer = params["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(3), (t, cfg.hidden_size))
    tables = ds.rope_tables(cfg, jnp.arange(t))
    q_nope, q_rope, c, kr = ds.attention_inputs(cfg, layer, u, tables)
    expanded = ds.causal_attention(cfg, q_nope, q_rope, c, kr, layer,
                                   impl="dense")
    leaf = jnp.broadcast_to(mla.pack_latent(c, kr)[None],
                            (t, t // 2, 2 * cfg.latent_width))
    for impl in ("dense", "pallas"):
        absorbed = ds.absorbed_attention(cfg, layer, q_nope, q_rope, leaf,
                                         jnp.arange(1, t + 1), impl=impl)
        assert float(jnp.abs(expanded).max()) > 1.0
        np.testing.assert_allclose(absorbed, expanded, atol=1e-5, rtol=0)


def test_rotary_turns_neighbouring_lanes(toy):
    """`rope_interleave`: lanes (2i, 2i + 1) are one pair, turned by `t
    theta^(-2i/R)`; the norm of each pair stays, and position 0 is the
    identity."""
    cfg, _ = toy
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 8))
    pos = jnp.array([0, 1, 7, 100, 4095])
    got = np.asarray(ds.rotate(x, ds.rope_tables(cfg, pos)))
    want = np.zeros_like(got)
    for i in range(4):
        ang = np.asarray(pos, np.float64) * 1e6 ** (-2 * i / 8)
        a, b = np.asarray(x[..., 2 * i]), np.asarray(x[..., 2 * i + 1])
        want[..., 2 * i] = a * np.cos(ang)[:, None] - b * np.sin(ang)[:, None]
        want[..., 2 * i + 1] = b * np.cos(ang)[:, None] \
            + a * np.sin(ang)[:, None]
    np.testing.assert_allclose(got, want, atol=2e-4)      # float32 angles
    np.testing.assert_array_equal(got[0], np.asarray(x[0]))


# -- the kernels --------------------------------------------------------------
def test_the_leaf_packs_two_positions_a_row():
    c = jnp.arange(2 * 6 * 4, dtype=jnp.float32).reshape(2, 6, 4)
    kr = -jnp.arange(2 * 6 * 2, dtype=jnp.float32).reshape(2, 6, 2)
    leaf = mla.pack_latent(c, kr)
    assert leaf.shape == (2, 3, 12)
    np.testing.assert_array_equal(
        leaf[1, 2], np.concatenate([c[1, 4], c[1, 5], kr[1, 4], kr[1, 5]]))
    back = mla.unpack_latent(leaf, 4)
    np.testing.assert_array_equal(back[0], c)
    np.testing.assert_array_equal(back[1], kr)
    # a row write touches its own half only; past the leaf it is dropped
    new_c, new_kr = jnp.full((2, 4), 7.0), jnp.full((2, 2), 9.0)
    wrote = mla.write_latent_row(leaf, jnp.array([3, 6]), new_c, new_kr)
    got_c, got_kr = mla.unpack_latent(wrote, 4)
    want_c, want_kr = np.array(c), np.array(kr)
    want_c[0, 3], want_kr[0, 3] = 7.0, 9.0
    np.testing.assert_array_equal(got_c, want_c)
    np.testing.assert_array_equal(got_kr, want_kr)


@pytest.mark.parametrize("dtype,block_k,tol", [
    (jnp.float32, 16, 2e-6), (jnp.float32, None, 2e-6),
    (jnp.bfloat16, 16, 2e-2)], ids=["f32_tiles", "f32_one_tile", "bf16"])
def test_decode_kernel_matches_masked_softmax_at_ragged_lengths(dtype,
                                                                block_k,
                                                                tol):
    """The kernel interpreted against a dense masked softmax over the
    latent, written here: lengths of 1, the full rung, one that is not a
    multiple of the tile (and odd: it ends inside a packed row), and 0
    (zeros), with garbage past each length that must not show. bfloat16
    operands round the weights of the second product to 8 bits (2e-2 at
    outputs of size 1)."""
    s, h, lat, rope, rung = 4, 4, 32, 8, 64
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    lengths = jnp.array([1, rung, 37, 0], jnp.int32)
    c = jax.random.normal(keys[0], (s, rung, lat), jnp.float32)
    kr = jax.random.normal(keys[1], (s, rung, rope), jnp.float32)
    q_lat = jax.random.normal(keys[2], (s, h, lat), jnp.float32)
    q_rope = jax.random.normal(keys[3], (s, h, rope), jnp.float32)
    past = jnp.arange(rung)[None, :, None] >= lengths[:, None, None]
    c_in, kr_in = (jnp.where(past, 1e4, a).astype(dtype) for a in (c, kr))
    got = mla.mla_attention_decode(
        q_lat.astype(dtype), q_rope.astype(dtype),
        mla.pack_latent(c_in, kr_in), lengths, 0.2, impl="pallas",
        block_k=block_k)
    cf, krf, qlf, qrf = (a.astype(dtype).astype(jnp.float32)
                         for a in (c, kr, q_lat, q_rope))
    score = 0.2 * (jnp.einsum("shl,scl->shc", qlf, cf)
                   + jnp.einsum("shr,scr->shc", qrf, krf))
    p = jax.nn.softmax(jnp.where(past[:, None, :, 0], -jnp.inf, score), -1)
    want = jnp.einsum("shc,scl->shl", jnp.nan_to_num(p), cf)
    assert got.dtype == dtype and got.shape == (s, h, lat)
    np.testing.assert_allclose(got[:3].astype(jnp.float32), want[:3],
                               atol=tol, rtol=0)
    assert not np.asarray(got[3].astype(jnp.float32)).any()
    # the XLA path keeps the same contract
    dense = mla.mla_attention_decode(
        q_lat.astype(dtype), q_rope.astype(dtype),
        mla.pack_latent(c_in, kr_in), lengths, 0.2, impl="dense")
    np.testing.assert_allclose(dense.astype(jnp.float32),
                               jnp.where(lengths[:, None, None] > 0, want,
                                         0), atol=tol, rtol=0)


def test_decode_tile_is_the_shared_rule_over_the_latent_lanes():
    """1024 positions (512 packed rows of 2304 B) at the published widths
    and the cell's rung; a rung nothing divides is one tile."""
    assert mla.latent_tile_positions(18432, 512, jnp.bfloat16) == 1024
    assert mla.latent_tile_positions(18432, 512, jnp.bfloat16) \
        == fa.decode_tile_rows(18432, 512, jnp.bfloat16)
    assert mla.latent_tile_positions(70, 32, jnp.float32) == 70


@pytest.mark.parametrize("native", [False, True], ids=["float32", "native"])
def test_flash_attention_takes_values_narrower_than_keys(native):
    """Keys of 24 and values of 16 (the expanded form's 192 and 128),
    causal, in tiles of 16; `native` leaves bfloat16 operands as they are
    for both products (float32 sums), as the prefill runs it."""
    dtype, tol = (jnp.bfloat16, 2e-2) if native else (jnp.float32, 2e-6)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k = (jax.random.normal(kk, (1, 3, 40, 24), jnp.float32).astype(dtype)
            for kk in keys[:2])
    v = jax.random.normal(keys[2], (1, 3, 40, 16), jnp.float32).astype(dtype)
    got = fa.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                             native=native)
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / 24 ** 0.5
    seen = jnp.arange(40)[None, :] <= jnp.arange(40)[:, None]
    want = jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), vf)
    assert got.shape == (1, 3, 40, 16) and got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=tol,
                               rtol=0)


@pytest.mark.parametrize("native,v_width", [(True, 24), (False, 16)],
                         ids=["native", "narrow_values"])
def test_flash_attention_forward_only_paths_say_so(native, v_width):
    """A gradient through either forward-only path raises at once, by name,
    and not somewhere inside Pallas."""
    q, k = (jnp.ones((1, 1, 16, 24), jnp.float32),) * 2
    v = jnp.ones((1, 1, 16, v_width), jnp.float32)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: fa.flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16,
            native=native).sum())(q)


# -- through the decoder's cache ------------------------------------------------
@pytest.mark.parametrize("attn_impl", ["dense", "pallas"])
def test_prefill_then_ten_steps_match_the_full_forward(toy, attn_impl):
    """Slots 2 and 0 of a 3-slot cache take prompts of 21 and 35 (buckets
    of 40) in the EXPANDED form and decode 10 greedy tokens in the ABSORBED
    form at DIFFERENT positions while slot 1 idles, the rung grown from 40
    to 64 after the fourth step: every step's logits are the reference's
    full forward at that position (logits, not tokens), so the two forms
    agree through the cache, row for row."""
    cfg, params = toy
    dec = MLADecoder(cfg, params, attn_impl=attn_impl)
    margs = dec.model_args()
    prefill, step = jax.jit(dec.prefill), jax.jit(dec.step)
    slots, prompts = (2, 0), (_ids(1, 21), _ids(2, 35))
    cache = dec.init_cache(3, 40)
    assert [l.shape for l in cache["kv"]] == [(3, 20, 80)] * 3
    seqs, tokens, got = {}, np.zeros(3, np.int32), {s: [] for s in slots}
    pos = np.zeros(3, np.int32)
    for slot, prompt in zip(slots, prompts):
        padded = np.zeros(40, np.int32)
        padded[:len(prompt)] = prompt
        cache, logits = prefill(margs, cache, np.int32(slot), padded,
                                np.int32(len(prompt)))
        got[slot].append(logits)
        tokens[slot], pos[slot] = int(np.argmax(logits)), len(prompt)
        seqs[slot] = list(prompt)
    for i in range(10):
        if i == 4:
            cache = dec.grow(cache, 64)
        logits, cache = step(margs, cache, tokens.copy(), pos.copy())
        for slot in slots:
            seqs[slot].append(int(tokens[slot]))
            got[slot].append(logits[slot])
            tokens[slot] = int(np.argmax(logits[slot]))
            pos[slot] += 1
    for slot, prompt in zip(slots, prompts):
        # causal: one full forward gives every step's reference
        ref = _reference(params, np.array(seqs[slot]))[0]
        np.testing.assert_allclose(np.stack(got[slot]),
                                   ref[len(prompt) - 1:], atol=LOGIT_TOL,
                                   rtol=0)
    counts = dict(zip(dec.counter_names, np.asarray(cache["counts"])))
    # 10 steps x 2 EXPERT layers (of 3) x 3 slots x 4 choices, all held
    assert counts["moe_pairs"] == 10 * 2 * 3 * 4
    # rows in use, all 3 layers: 22..31, 36..45 and the idle slot's 1
    assert counts["mla_rows_attended"] == 3 * (265 + 405 + 10)
    # each rung is one tile: 4 steps of 3 slots at 40, 6 at 64
    assert counts["mla_rows_read"] == 3 * 3 * (4 * 40 + 6 * 64)


def test_rows_read_are_the_rows_in_use_rounded_up_to_the_tile(toy):
    """`mla_rows_read` on a rung the kernel reads in several tiles (4096
    positions of 40 float32 values): `ceil(in_use / tile) * tile` a slot a
    layer; `mla_rows_attended` the rows themselves."""
    cfg, params = toy
    dec = MLADecoder(cfg, params, attn_impl="dense")
    tile = mla.latent_tile_positions(4096, 32, jnp.float32)
    assert tile in (512, 1024, 2048)
    cache = dec.init_cache(3, 4096)
    pos = np.array([21, 0, tile + 40], np.int32)
    _, cache = jax.jit(dec.step)(dec.model_args(), cache,
                                 np.ones(3, np.int32), pos)
    counts = dict(zip(dec.counter_names, np.asarray(cache["counts"])))
    assert counts["mla_rows_attended"] == 3 * (22 + 1 + tile + 41)
    assert counts["mla_rows_read"] == 3 * (tile + tile + 2 * tile)


def test_grow_pads_the_latent_leaves(toy):
    cfg, params = toy
    dec = MLADecoder(cfg, params)
    cache = jax.tree_util.tree_map(
        lambda l: jnp.arange(l.size, dtype=jnp.float32).reshape(
            l.shape).astype(l.dtype), dec.init_cache(2, 8))
    grown = dec.grow(cache, 24)
    for old, new in zip(cache["kv"], grown["kv"]):
        assert new.shape == (2, 12, 80)
        np.testing.assert_array_equal(new[:, :old.shape[1]], old)
        assert not np.asarray(new[:, old.shape[1]:]).any()
    assert grown["counts"] is cache["counts"]
    assert dec.uses_cache_rungs and not dec.supports_draft
    with pytest.raises(ValueError, match="must be even"):
        dec.init_cache(2, 9)
    with pytest.raises(ValueError, match="attn_impl"):
        MLADecoder(cfg, params, attn_impl="flash")


# -- through the server ---------------------------------------------------------
def test_server_streams_equal_the_decoders_own_and_never_compile(toy):
    """Greedy streams through `GenerationServer` (two requests at once, a
    rung grown mid-service) are what the decoder's own prefill and steps
    give, token for token; past warm-up nothing traces or compiles."""
    cfg, params = toy
    dec = MLADecoder(cfg, params)
    srv = GenerationServer(dec, slots=2, cache_lengths=[32, 64],
                           prompt_buckets=[24, 40], method="greedy",
                           max_new_tokens=8, seed=0)
    prompts = [_ids(4, 20), _ids(5, 33)]
    try:
        warm = srv.warmup()
        assert warm["compiled"] + warm["from_disk"] == warm["executables"]
        traces, compiles = srv._store.trace_calls, \
            srv._store.stats["compiles"]
        handles = [srv.submit(p, max_new_tokens=10) for p in prompts]
        streams = [h.result(timeout=300) for h in handles]
        assert srv._rung == 64                         # grew mid-service
        assert srv._store.trace_calls == traces
        assert srv._store.stats["compiles"] == compiles
        st = srv.status()
    finally:
        srv.shutdown()
    margs = dec.model_args()
    for prompt, stream in zip(prompts, streams):
        cache = dec.init_cache(1, 64)
        padded = np.zeros(40, np.int32)
        padded[:len(prompt)] = prompt
        cache, logits = dec.prefill(margs, cache, np.int32(0), padded,
                                    np.int32(len(prompt)))
        own = [int(np.argmax(logits))]
        for i in range(9):
            logits, cache = dec.step(
                margs, cache, np.array(own[-1:], np.int32),
                np.array([len(prompt) + i], np.int32))
            own.append(int(np.argmax(logits[0])))
        assert list(stream) == own
    assert st["decoder"] == "MLADecoder" and st["state"] == "serving"
    # 2 expert layers x 2 slots x 4 choices a step
    assert st["moe_pairs"] == 2 * 2 * 4 * st["steps"] > 0
    # each slot's one-tile rung a layer a step, and no more rows than that
    assert 3 * 2 * 32 * st["steps"] <= st["mla_rows_read"] \
        <= 3 * 2 * 64 * st["steps"]
    assert 0 < st["mla_rows_attended"] < st["mla_rows_read"]


# -- the expert layer -----------------------------------------------------------
def _looped(layer, g, cfg, bias):
    """The reference's way: a sort for the choice, every held expert over
    every token, masked; the shared experts as one SwiGLU."""
    score = jax.nn.sigmoid(g @ layer["router"])
    idx = jnp.argsort(-(score + bias), axis=-1, stable=True)[
        :, :cfg.num_experts_per_tok]
    val = jnp.take_along_axis(score, idx, -1)
    wts = cfg.routed_scaling_factor * val / val.sum(-1, keepdims=True)
    first, count = cfg.experts_held
    out = ds.swiglu(g, layer["s_gate"], layer["s_up"], layer["s_down"])
    for j in range(count):
        w_tok = jnp.where(idx == first + j, wts, 0.0).sum(-1)
        out = out + w_tok[:, None] * ds.swiglu(
            g, layer["w_gate"][j], layer["w_up"][j], layer["w_down"][j])
    return out, idx


def test_the_bias_changes_the_choice_and_not_the_weights(toy):
    """`noaux_tc`: the correction bias picks the experts, the sigmoid
    scores of the picked weigh them. A bias of +1 on experts 12-15 (scores
    lie in 0..1) makes them every token's choice; the weights still sum to
    `routed_scaling_factor` and are the scores', not the biased ones."""
    cfg, params = toy
    layer = params["layers"][1]
    g = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.hidden_size))
    bias = jnp.zeros((16,)).at[12:].set(1.0)
    _, plain_idx = _looped(layer, g, cfg, layer["router_bias"])
    want, idx = _looped(layer, g, cfg, bias)
    assert sorted(np.unique(idx).tolist()) == [12, 13, 14, 15]
    assert len(np.unique(plain_idx)) > 4
    got, counts = ds.moe(cfg, {**layer, "router_bias": bias}, g)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert counts.tolist() == [24 * 4, 4, 24]
    # the weights alone: the routed part of a layer whose experts all give
    # their input's first lanes back sums to the scaling factor
    scores = ds.router_scores(layer, g)
    out, _ = routed_experts(
        jnp.ones((24, 8)), scores, bias, jnp.ones((16, 8, 8)),
        jnp.eye(8)[None].repeat(16, 0), (0, 16), 4, 2.448,
        lambda x: x)
    np.testing.assert_allclose(out, 8 * 2.448 * jnp.ones((24, 8)),
                               rtol=1e-5)


def test_long_sequences_go_through_the_experts_in_runs(toy, monkeypatch):
    cfg, params = toy
    layer = params["layers"][2]
    g = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.hidden_size))
    whole, counts = ds.moe(cfg, layer, g)
    monkeypatch.setattr(ds, "MOE_CHUNK", 16)
    runs, run_counts = ds.moe(cfg, layer, g)
    np.testing.assert_allclose(runs, whole, atol=1e-5, rtol=0)
    assert run_counts[0] == counts[0] == 64 * 4
    assert run_counts[2] <= counts[2]


def test_eight_shares_of_sixteen_add_up_to_the_uncut_reference():
    """The cut, tied to the model: a dense layer and ONE expert layer with
    the published 128 experts and 6 a token. Eight chips hold 16 experts
    each; what each computes of the routed experts (`routed_experts` with
    its `held`), added up over the eight, with what every chip computes
    alike (attention, the shared experts) counted ONCE, is the UNCUT
    reference's layer: the logits that follow are the reference's with all
    128 experts held."""
    whole = {**TOY, "num_hidden_layers": 2, "n_routed_experts": 128,
             "num_experts_per_tok": 6, "held": {"experts": [0, 128]}}
    cfg = ds.DeepseekV3Config.from_dict(whole)
    params = _sharpened(ds.init_params(cfg, jax.random.PRNGKey(11)))
    ids = _ids(6, 40)
    tables = ds.rope_tables(cfg, jnp.arange(40))
    x = params["embed"][ids]
    x, _ = ds.apply_layer(cfg, 0, params["layers"][0], x, tables)
    layer = params["layers"][1]
    u = ds.rms_norm(x, layer["norm1"], cfg.rms_norm_eps)
    h = x + ds.causal_attention(
        cfg, *ds.attention_inputs(cfg, layer, u, tables), layer) @ layer["o"]
    g = ds.rms_norm(h, layer["norm2"], cfg.rms_norm_eps)
    shared = ds.swiglu(g, layer["s_gate"], layer["s_up"], layer["s_down"])
    parts, pairs = [], 0
    for first in range(0, 128, 16):
        share = ds.DeepseekV3Config.from_dict(whole,
                                              experts_held=(first, 16))
        held = {**layer, **{name: layer[name][first:first + 16]
                            for name in ("w_gate", "w_up", "w_down")}}
        out, counts = ds.moe(share, held, g)
        parts.append(out - shared)           # a chip's ROUTED part alone
        pairs += int(counts[0])
    assert pairs == 40 * 6                   # every pair on exactly one chip
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)
    got = ds.logits(cfg, params, h + sum(parts) + shared)
    want = _reference(params, ids, family.reference_sizes(whole))[0]
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    # and one share alone is the reference given that share
    one = {**whole, "n_routed_experts": 16, "held": {"experts": [32, 48]}}
    held = {**layer, **{name: layer[name][32:48]
                        for name in ("w_gate", "w_up", "w_down")}}
    cut = {**params, "layers": [params["layers"][0], held]}
    got = ds.forward(ds.DeepseekV3Config.from_dict(
        whole, experts_held=(32, 16)), cut, ids)
    np.testing.assert_allclose(
        got, _reference(cut, ids, family.reference_sizes(one))[0],
        atol=LOGIT_TOL, rtol=0)
