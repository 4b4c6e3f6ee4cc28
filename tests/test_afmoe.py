"""The AFMoE language model (models/afmoe.py, AfmoeDecoder, the banded
prefill of `flash_attention(..., window=)`) against the plain reference
that the benchmark keeps (benchmarks/families/afmoe_serve.py: a band or a
triangle made by `where`, rotary in halves on the sliding layers alone, the
router's choice by a sort, looped experts).

Toy widths that keep the published model's shapes: hidden 64, 4 query
heads over 2 KV heads of 16, a gate of 64, a window of 12 rows, layers S F
S F (a full layer every second: the published period is 4), two leading
dense layers of 96, then two expert layers of 16 experts of width 32, 4 a
token, one shared. Float32 on the CPU at the highest matmul precision
(conftest), so the tolerances below are summation-order noise, not a
precision. The fixture's head is drawn ten times larger than
`init_params` draws it (logits of size 6, not 0.6) and its q norm's weight
at 4 (not `SEEDED_Q_NORM`), so that what a position attends decides its
logits at these widths."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import afmoe_serve as family
from deeplearning4j_tpu.generation.decode import AfmoeDecoder
from deeplearning4j_tpu.generation.server import GenerationServer
from deeplearning4j_tpu.models import afmoe, decoder_common
from deeplearning4j_tpu.parallel.moe import routed_experts

fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

TOY = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=16,
    num_experts_per_tok=4, num_shared_experts=1, num_dense_layers=2,
    sliding_window=12, global_attn_every_n_layers=2,
    layer_types=["sliding_attention", "full_attention"] * 3,
    route_scale=2.826, score_func="sigmoid", route_norm=True, n_group=1,
    topk_group=1, num_expert_groups=1, num_limited_groups=1,
    rope_scaling=None, rope_theta=1e4, rms_norm_eps=1e-5, mup_enabled=True,
    hidden_act="silu", tie_word_embeddings=False, held={"experts": [0, 16]})
W = TOY["sliding_window"]
#: float32 both sides, different summation orders (an online softmax in
#: tiles against whole rows, a ring's rows in another order than the
#: positions', grouped against looped experts): logits of size 6 agree to
#: 4e-6. One precision lower fails it by four orders
#: (`test_lower_precision_reference_fails_the_tolerance`)
LOGIT_TOL = 2e-5


def _sharpened(params):
    """Logits of size 6 and attention that decides them (the module's
    docstring)."""
    return {**params, "head": params["head"] * 10.0, "layers": [
        {**layer, "q_norm": layer["q_norm"] * 4.0 / afmoe.SEEDED_Q_NORM}
        for layer in params["layers"]]}


@pytest.fixture(scope="module")
def toy():
    cfg = afmoe.AfmoeConfig.from_dict(TOY)
    return cfg, _sharpened(afmoe.init_params(cfg, jax.random.PRNGKey(7)))


def _reference(params, ids, sizes=None, **kw):
    return family.reference_logits(
        params, jnp.atleast_2d(jnp.asarray(ids)),
        sizes or family.reference_sizes(TOY), **kw)


def _ids(seed, *shape):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"], shape).astype(np.int32)


# -- the full forward ---------------------------------------------------------
@pytest.mark.parametrize("impl,t", [("dense", 40), ("dense", 29),
                                    ("pallas", 40), ("pallas", 53)],
                         ids=["dense", "dense_odd", "kernel",
                              "kernel_longer"])
def test_forward_matches_reference(toy, impl, t, monkeypatch):
    """The program's forward (sliding layers through the banded kernel in
    tiles of 16, full layers through the causal one) against the
    reference's."""
    cfg, params = toy
    monkeypatch.setattr(afmoe, "PREFILL_BLOCK", 16)
    ids = _ids(t, 2, t)
    got = jax.jit(lambda p, x: afmoe.forward(cfg, p, x, impl=impl))(params,
                                                                      ids)
    want = _reference(params, ids)
    assert float(jnp.abs(want).max()) > 3.0
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("fault", family.FAULTS)
def test_planted_faults_move_the_reference(toy, fault):
    """The reference's planted faults (the controls of the cell's `check()`
    on the chip: `benchmarks/tests/test_rehearsal_window.py`) are not the
    reference: each moves every position past the window."""
    _, params = toy
    ids = _ids(5, 40)
    moved = np.abs(_reference(params, ids, fault=fault)
                   - _reference(params, ids)).max(-1)[0]
    assert float(moved[W + 2:].min()) > 100 * LOGIT_TOL


def test_the_window_and_the_full_layers_decide_these_logits(toy):
    """What the comparisons here are worth: a position that attends other
    rows (the sequence's first token changed) has other logits at EVERY
    later position, past the window too (through the full layers)."""
    cfg, params = toy
    ids = _ids(3, 40)
    other = ids.copy()
    other[0] = (ids[0] + 1) % TOY["vocab_size"]
    base = afmoe.forward(cfg, params, ids)
    moved = jnp.abs(base - afmoe.forward(cfg, params, other)).max(-1)
    assert float(moved[W + 2:].min()) > 100 * LOGIT_TOL


def test_lower_precision_reference_fails_the_tolerance(toy):
    """What `LOGIT_TOL` is worth: the reference one precision down (float8
    weights, activations and cached rows) misses it by orders."""
    _, params = toy
    ids = _ids(5, 1, 40)
    lower = _reference(params, ids, lower=True)
    assert float(jnp.abs(lower - _reference(params, ids)).max()) > 1e-2


def test_layers_zero_and_one_are_dense_and_layer_two_is_not(toy):
    cfg, params = toy
    assert [cfg.is_dense(li) for li in range(4)] == [True, True, False,
                                                     False]
    assert [cfg.is_sliding(li) for li in range(4)] == [True, False, True,
                                                       False]
    for layer in params["layers"][:2]:
        assert {"d_gate", "d_up", "d_down"} <= set(layer)
        assert "router" not in layer
    for layer in params["layers"][2:]:
        assert {"router", "router_bias", "w_gate", "s_gate"} <= set(layer)
        assert "d_gate" not in layer
        assert layer["w_gate"].shape == (16, 64, 32)
        assert layer["s_up"].shape == (64, 32)              # ONE shared
    # and both kinds are computed: each moves the logits
    ids = _ids(2, 24)
    base = afmoe.forward(cfg, params, ids)
    for li, name in ((1, "d_down"), (2, "s_down"), (3, "w_down")):
        layers = list(params["layers"])
        layers[li] = {**layers[li], name: layers[li][name] * 0}
        assert float(jnp.abs(afmoe.forward(
            cfg, {**params, "layers": layers}, ids) - base).max()) > 1e-3


def test_config_refuses_what_it_cannot_run():
    for key, value in (("score_func", "softmax"), ("route_norm", False),
                       ("rope_scaling", {"type": "yarn", "factor": 4}),
                       ("n_group", 8), ("num_expert_groups", 2),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            afmoe.AfmoeConfig.from_dict({**TOY, key: value})
    for types in (["sliding_attention"] * 6, ["full_attention",
                                               "sliding_attention"] * 3,
                  ["sliding_attention", "full_attention"]):
        with pytest.raises(ValueError, match="layer_types"):
            afmoe.AfmoeConfig.from_dict({**TOY, "layer_types": types})
    with pytest.raises(ValueError, match="experts_held"):
        afmoe.AfmoeConfig.from_dict(TOY, experts_held=(8, 16))
    cfg = afmoe.AfmoeConfig.from_dict(TOY, experts_held=(4, 4))
    assert (cfg.q_width, cfg.kv_width, cfg.routed_scaling_factor) \
        == (64, 32, 2.826)
    assert cfg.embed_scale == 8.0
    assert afmoe.AfmoeConfig.from_dict(
        {**TOY, "mup_enabled": False}).embed_scale == 1.0


def test_rotary_turns_halves_and_only_on_sliding_layers(toy):
    """Lane j turns with lane j + D/2 by `t theta^(-2j/D)`, position 0 is
    the identity; a full layer's q and k are NOT rotated."""
    cfg, params = toy
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 16))
    pos = jnp.array([0, 1, 7, 100, 4095])
    got = np.asarray(afmoe.rotate(x, afmoe.rope_tables(cfg, pos)))
    want = np.zeros_like(got)
    for j in range(8):
        ang = np.asarray(pos, np.float64) * 1e4 ** (-2 * j / 16)
        a, b = np.asarray(x[..., j]), np.asarray(x[..., j + 8])
        want[..., j] = a * np.cos(ang)[:, None] - b * np.sin(ang)[:, None]
        want[..., j + 8] = b * np.cos(ang)[:, None] \
            + a * np.sin(ang)[:, None]
    np.testing.assert_allclose(got, want, atol=2e-4)      # float32 angles
    np.testing.assert_array_equal(got[0], np.asarray(x[0]))
    u = jax.random.normal(jax.random.PRNGKey(1), (6, 64))
    tables = afmoe.rope_tables(cfg, jnp.arange(6) + 30)
    still = afmoe.rope_tables(cfg, jnp.zeros(6, jnp.int32))
    for li in (0, 1):
        turned = afmoe.attention_inputs(cfg, li, params["layers"][li], u,
                                        tables)
        plain = afmoe.attention_inputs(cfg, li, params["layers"][li], u,
                                       still)
        same = [np.allclose(a, b) for a, b in zip(turned, plain)]
        assert same == [not cfg.is_sliding(li), not cfg.is_sliding(li),
                        True, True], li


# -- the banded prefill kernel ------------------------------------------------
def _banded(q, k, v, window):
    """A dense banded softmax, written here: query t over keys t - window <
    s <= t."""
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[3] ** 0.5
    tq, tk = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = (tk <= tq) & (tk > tq - window)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("t,window,bq,bk", [
    (37, 10, 16, 16), (64, 17, 32, 16), (64, 16, 16, 32), (50, 100, 16, 16),
    (40, 1, 16, 8)], ids=["ragged_prompt", "ragged_window",
                          "wide_key_tiles", "window_past_the_prompt",
                          "own_row_only"])
def test_banded_kernel_matches_a_dense_banded_softmax(t, window, bq, bk):
    """Interpreted, float32: a prompt that is not a multiple of the tile,
    a window that is not one, a window past the prompt (the causal
    triangle), key tiles wider than query tiles, a window of one row."""
    keys = jax.random.split(jax.random.PRNGKey(t + window), 3)
    q, k, v = (jax.random.normal(kk, (1, 3, t, 16)) for kk in keys)
    got = fa.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                             window=window)
    np.testing.assert_allclose(got, _banded(q, k, v, window), atol=2e-6,
                               rtol=0)


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_grouped_query_band_through_the_model(toy, impl, monkeypatch):
    """The model's sliding attention, 4 query heads over 2 KV heads (the
    kernel path repeats the KV heads, the dense one groups them), against
    the dense banded softmax with every query head given its KV head."""
    cfg, _ = toy
    monkeypatch.setattr(afmoe, "PREFILL_BLOCK", 16)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (37, 4, 16))
    k, v = (jax.random.normal(kk, (37, 32)) for kk in keys[1:])
    got = afmoe.sequence_attention(cfg, 0, q, k, v, impl=impl)
    kh, vh = (jnp.repeat(a.reshape(37, 2, 16), 2, axis=1).transpose(
        1, 0, 2)[None] for a in (k, v))
    want = _banded(q.transpose(1, 0, 2)[None], kh, vh, W)
    np.testing.assert_allclose(
        got, want[0].transpose(1, 0, 2).reshape(37, 64), atol=2e-6, rtol=0)


def _pallas_grid(fn, *args):
    """The grid of the one `pallas_call` in `fn`'s program."""
    eqns = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "custom_vjp_call"
            or e.primitive.name == "custom_vjp_call_jaxpr"]
    jaxpr = eqns[0].params.get("call_jaxpr") or eqns[0].params["fun_jaxpr"]
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return tuple(calls[0].params["grid_mapping"].grid)


def test_tiles_below_the_band_are_neither_visited_nor_computed():
    """At 128 rows in tiles of 16 and a window of 20, a q tile's band
    touches at most 3 key tiles of 8: the grid's k axis has 3 steps, not 8
    (the causal call's), so 8 x 3 steps a head in place of 8 x 8. And the
    keys and values of every tile wholly below a band are NaN: a step that
    fetched such a tile into its arithmetic would carry NaN out; none
    does."""
    t, window, block = 128, 20, 16
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, t, 16)) for kk in keys)

    def banded(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=block,
                                  block_k=block, window=window)

    def causal(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=block,
                                  block_k=block, native=True)

    assert _pallas_grid(banded, q, k, v) == (2, 8, 3)
    assert _pallas_grid(causal, q, k, v) == (2, 8, 8)
    assert fa.band_tiles(8, block, block, window, 8) == 3
    # the last q tile (rows 112..127) needs keys 93..127: tiles 5, 6, 7
    assert fa._band_span(7, block, block, window, 8) == (5, 7)
    # poison: keys 0..15 lie below every band of q tiles 3 and up, and a
    # NaN in them would reach those tiles' rows through 0 x NaN
    poisoned = [a.at[:, :, :block].set(jnp.nan) for a in (k, v)]
    got = banded(q, *poisoned)
    want = _banded(q, k, v, window)
    assert np.isfinite(np.asarray(got[:, :, 3 * block:])).all()
    np.testing.assert_allclose(got[:, :, 3 * block:],
                               want[:, :, 3 * block:], atol=2e-6, rtol=0)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, window=window)


def test_the_band_is_forward_only():
    q = jnp.ones((1, 1, 16, 16), jnp.float32)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: fa.flash_attention(
            q, q, q, causal=True, block_q=16, block_k=16,
            window=4).sum())(q)


# -- through the decoder's cache ----------------------------------------------
def _serve_by_hand(dec, cfg, prompts, slots, bucket, rung, steps,
                   grow_at=None, grown=None):
    """Prefill `prompts` into `slots` of a 3-slot cache (padded to
    `bucket`), then `steps` greedy decode steps at different positions,
    growing the rung once; every logit vector served, a slot at a time."""
    margs = dec.model_args()
    prefill, step = jax.jit(dec.prefill), jax.jit(dec.step)
    cache = dec.init_cache(3, rung)
    seqs, tokens, got = {}, np.zeros(3, np.int32), {s: [] for s in slots}
    pos = np.zeros(3, np.int32)
    for slot, prompt in zip(slots, prompts):
        padded = np.zeros(bucket, np.int32)
        padded[:len(prompt)] = prompt
        cache, logits = prefill(margs, cache, np.int32(slot), padded,
                                np.int32(len(prompt)))
        got[slot].append(logits)
        tokens[slot], pos[slot] = int(np.argmax(logits)), len(prompt)
        seqs[slot] = list(prompt)
    for i in range(steps):
        if i == grow_at:
            cache = dec.grow(cache, grown)
        logits, cache = step(margs, cache, tokens.copy(), pos.copy())
        for slot in slots:
            seqs[slot].append(int(tokens[slot]))
            got[slot].append(logits[slot])
            tokens[slot] = int(np.argmax(logits[slot]))
            pos[slot] += 1
    return cache, seqs, got


@pytest.mark.parametrize("attn_impl", ["dense", "pallas"])
def test_prefill_then_ten_steps_match_the_full_forward(toy, attn_impl,
                                                       monkeypatch):
    """Slots 2 and 0 of a 3-slot cache take prompts of 7 (under the window
    of 12) and 17 (not a multiple of it), both padded to a bucket of 24,
    and decode 10 greedy tokens at DIFFERENT positions while slot 1 idles:
    slot 2's steps cross its ring's first wrap (position 12 at row 0),
    slot 0's its second (24); the rung grows from 24 to 48 after the
    fourth step (the full layers' leaves; the rings stay at 12). Every
    step's logits are the reference's full forward at that position
    (logits, not tokens)."""
    cfg, params = toy
    monkeypatch.setattr(afmoe, "PREFILL_BLOCK", 16)
    dec = AfmoeDecoder(cfg, params, attn_impl=attn_impl)
    slots, prompts = (2, 0), (_ids(1, 7), _ids(2, 17))
    assert [l.shape for l in dec.init_cache(3, 24)["k"]] \
        == [(3, 12, 32), (3, 24, 32)] * 2
    cache, seqs, got = _serve_by_hand(dec, cfg, prompts, slots, 24, 24, 10,
                                      grow_at=4, grown=48)
    assert [l.shape for l in cache["v"]] == [(3, 12, 32), (3, 48, 32)] * 2
    for slot, prompt in zip(slots, prompts):
        # causal: one full forward gives every step's reference
        ref = _reference(params, np.array(seqs[slot]))[0]
        np.testing.assert_allclose(np.stack(got[slot]),
                                   ref[len(prompt) - 1:], atol=LOGIT_TOL,
                                   rtol=0)
    counts = dict(zip(dec.counter_names, np.asarray(cache["counts"])))
    # 10 steps x 2 EXPERT layers (of 4) x 3 slots x 4 choices, all held
    assert counts["moe_pairs"] == 10 * 2 * 3 * 4
    # rows in use a step: min(pos + 1, 12) on the 2 rings, pos + 1 on the
    # 2 full layers; slot 2 at 7..16, slot 0 at 17..26, slot 1 idle at 0
    ring = sum(min(p + 1, 12) for p in range(7, 17)) + 10 * 12 + 10
    full = sum(p + 1 for p in range(7, 17)) \
        + sum(p + 1 for p in range(17, 27)) + 10
    assert counts["window_rows_attended"] == 2 * ring
    assert counts["global_rows_attended"] == 2 * full


def test_the_ring_holds_the_last_window_of_real_positions(toy):
    """After a prefill of 17 real positions in a bucket of 24, ring row r
    holds position `16 - ((16 - r) mod 12)`: rows 0..4 positions 12..16,
    rows 5..11 positions 5..11, and NO padding row; the full layer's leaf
    holds rows 0..16 as the prompt's own. A prompt of 7 fills rows 0..6."""
    cfg, params = toy
    dec = AfmoeDecoder(cfg, params, attn_impl="dense")
    prompt = _ids(4, 17)
    padded = np.zeros(24, np.int32)
    padded[:17] = prompt
    cache, _ = jax.jit(dec.prefill)(dec.model_args(), dec.init_cache(2, 24),
                                    np.int32(1), padded, np.int32(17))
    _, states = afmoe.encode(cfg, params, jnp.asarray(padded), impl="dense")
    at = np.array([16 - (16 - r) % 12 for r in range(12)])
    assert at.tolist() == [12, 13, 14, 15, 16, 5, 6, 7, 8, 9, 10, 11]
    # (the jitted prefill's rows against an eager encode: 1e-5)
    for got, want in ((cache["k"][0][1], states[0][0][at]),
                      (cache["v"][2][1], states[2][1][at]),
                      (cache["k"][1][1][:17], states[1][0][:17])):
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert not np.asarray(cache["k"][0][0]).any()          # slot 0 untouched
    short = np.zeros(24, np.int32)
    short[:7] = prompt[:7]
    cache, _ = jax.jit(dec.prefill)(dec.model_args(), dec.init_cache(1, 24),
                                    np.int32(0), short, np.int32(7))
    _, states = afmoe.encode(cfg, params, jnp.asarray(short), impl="dense")
    np.testing.assert_allclose(cache["k"][2][0][:7], states[2][0][:7],
                               atol=1e-5, rtol=0)


def test_grow_pads_the_full_leaves_and_a_short_ring(toy):
    """A rung of 8 gives rings of 8 rows (shorter than the window); growing
    to 24 pads the rings to 12 and the full leaves to 24, the rows held
    unchanged; a ring of 12 is never grown past it."""
    cfg, params = toy
    dec = AfmoeDecoder(cfg, params)
    cache = jax.tree_util.tree_map(
        lambda l: jnp.arange(l.size, dtype=jnp.float32).reshape(
            l.shape).astype(l.dtype), dec.init_cache(2, 8))
    grown = dec.grow(cache, 24)
    again = dec.grow(grown, 48)
    for li, (old, new, last) in enumerate(zip(cache["k"], grown["k"],
                                              again["k"])):
        rows = (12, 12) if cfg.is_sliding(li) else (24, 48)
        assert (new.shape[1], last.shape[1]) == rows
        np.testing.assert_array_equal(new[:, :8], old)
        assert not np.asarray(new[:, 8:]).any()
    assert grown["counts"] is cache["counts"]
    assert dec.uses_cache_rungs and not dec.supports_draft
    with pytest.raises(ValueError, match="attn_impl"):
        AfmoeDecoder(cfg, params, attn_impl="flash")


def test_a_ring_shorter_than_the_window_serves_across_the_grow(toy):
    """Rung 8, then 24: the rings start at 8 rows (positions at their own
    rows, none wrapped) and grow to the window's 12, where steps then
    wrap. Logits stay the reference's."""
    cfg, params = toy
    dec = AfmoeDecoder(cfg, params, attn_impl="dense")
    prompt = _ids(6, 5)
    cache, seqs, got = _serve_by_hand(dec, cfg, (prompt,), (1,), 8, 8, 12,
                                      grow_at=2, grown=24)
    ref = _reference(params, np.array(seqs[1]))[0]
    np.testing.assert_allclose(np.stack(got[1]), ref[4:], atol=LOGIT_TOL,
                               rtol=0)


# -- through the server -------------------------------------------------------
def test_server_streams_equal_the_decoders_own_and_never_compile(toy):
    """Greedy streams through `GenerationServer` (two requests at once, one
    prompt past the window, a rung grown mid-service) are what the
    decoder's own prefill and steps give, token for token; past warm-up
    nothing traces or compiles."""
    cfg, params = toy
    dec = AfmoeDecoder(cfg, params)
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
    assert st["decoder"] == "AfmoeDecoder" and st["state"] == "serving"
    # 2 expert layers x 2 slots x 4 choices a step
    assert st["moe_pairs"] == 2 * 2 * 4 * st["steps"] > 0
    # a ring holds at most 12 rows in use a slot, a full leaf up to 64
    assert 0 < st["window_rows_attended"] <= 2 * 2 * 12 * st["steps"]
    assert st["window_rows_attended"] < st["global_rows_attended"] \
        <= 2 * 2 * 64 * st["steps"]


# -- the expert layer ---------------------------------------------------------
def test_the_bias_changes_the_choice_and_not_the_weights(toy):
    """The correction bias picks the experts, the sigmoid scores of the
    picked weigh them. A bias of +1 on experts 12-15 makes them every
    token's choice; the weights still sum to `route_scale`."""
    cfg, params = toy
    layer = params["layers"][2]
    g = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.hidden_size))
    bias = jnp.zeros((16,)).at[12:].set(1.0)
    score = jax.nn.sigmoid(g @ layer["router"])
    plain = jnp.argsort(-(score + layer["router_bias"]), -1)[:, :4]
    assert len(np.unique(plain)) > 4
    got, counts = decoder_common.moe(cfg, {**layer, "router_bias": bias}, g)
    val = score[:, 12:]
    wts = 2.826 * val / val.sum(-1, keepdims=True)
    want = decoder_common.swiglu(g, layer["s_gate"], layer["s_up"],
                                 layer["s_down"])
    for j in range(4):
        want = want + wts[:, j:j + 1] * decoder_common.swiglu(
            g, layer["w_gate"][12 + j], layer["w_up"][12 + j],
            layer["w_down"][12 + j])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert counts.tolist() == [24 * 4, 4, 24]
    out, _ = routed_experts(
        jnp.ones((24, 8)), score, bias, jnp.ones((16, 8, 8)),
        jnp.eye(8)[None].repeat(16, 0), (0, 16), 4, 2.826, lambda x: x)
    np.testing.assert_allclose(out, 8 * 2.826 * jnp.ones((24, 8)),
                               rtol=1e-5)


def test_eight_shares_of_sixteen_add_up_to_the_uncut_reference():
    """The cut, tied to the model: two dense layers and ONE expert layer
    with the published 128 experts and 8 a token. Eight chips hold 16
    experts each; what each computes of the routed experts, added up over
    the eight, with what every chip computes alike (attention, the shared
    expert) counted ONCE, is the UNCUT reference's layer: the logits that
    follow are the reference's with all 128 experts held."""
    whole = {**TOY, "num_hidden_layers": 3, "num_experts": 128,
             "num_experts_per_tok": 8, "held": {"experts": [0, 128]}}
    cfg = afmoe.AfmoeConfig.from_dict(whole)
    params = _sharpened(afmoe.init_params(cfg, jax.random.PRNGKey(11)))
    ids = _ids(6, 40)
    tables = afmoe.rope_tables(cfg, jnp.arange(40))
    x = afmoe.embed(cfg, params, ids)
    for li in (0, 1):
        x, _ = afmoe.apply_layer(cfg, li, params["layers"][li], x, tables)
    layer = params["layers"][2]
    u = afmoe.rms_norm(x, layer["norm_in"], cfg.rms_norm_eps)
    q, k, v, g = afmoe.attention_inputs(cfg, 2, layer, u, tables)
    h = afmoe.attention_output(
        cfg, layer, x, afmoe.sequence_attention(cfg, 2, q, k, v), g)
    m = afmoe.rms_norm(h, layer["norm_pre_mlp"], cfg.rms_norm_eps)
    shared = decoder_common.swiglu(m, layer["s_gate"], layer["s_up"],
                                   layer["s_down"])
    parts, pairs = [], 0
    for first in range(0, 128, 16):
        share = afmoe.AfmoeConfig.from_dict(whole, experts_held=(first, 16))
        held = {**layer, **{name: layer[name][first:first + 16]
                            for name in ("w_gate", "w_up", "w_down")}}
        out, counts = decoder_common.moe(share, held, m)
        parts.append(out - shared)           # a chip's ROUTED part alone
        pairs += int(counts[0])
    assert pairs == 40 * 8                   # every pair on exactly one chip
    assert all(float(jnp.abs(p).max()) > 1e-2 * float(jnp.abs(shared).max())
               for p in parts)
    y = h + afmoe.rms_norm(sum(parts) + shared, layer["norm_post_mlp"],
                           cfg.rms_norm_eps)
    got = afmoe.logits(cfg, params, y)
    want = _reference(params, ids, family.reference_sizes(whole))[0]
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    # and one share alone is the reference given that share
    one = {**whole, "num_experts": 16, "held": {"experts": [32, 48]}}
    held = {**layer, **{name: layer[name][32:48]
                        for name in ("w_gate", "w_up", "w_down")}}
    cut = {**params, "layers": params["layers"][:2] + [held]}
    got = afmoe.forward(afmoe.AfmoeConfig.from_dict(
        whole, experts_held=(32, 16)), cut, ids)
    np.testing.assert_allclose(
        got, _reference(cut, ids, family.reference_sizes(one))[0],
        atol=LOGIT_TOL, rtol=0)
