"""The LFM2-MoE language model (models/lfm2_moe.py, Lfm2MoeDecoder, the
conv tail's two steps in `decoder_common`) against the plain reference that
the benchmark keeps (benchmarks/families/lfm2_moe_serve.py: the
convolution an explicit sum of its taps over a zero-padded sequence, a
causal triangle made by `where`, rotary in halves, the router's choice by a
sort, looped experts).

Toy widths that keep the published model's shapes: hidden 64, 4 query
heads over 2 KV heads of 16, a convolution of 3 taps, the published
pattern's first two periods C C A C C C A C, two leading dense layers of
96, then six expert layers of 8 experts of width 32, 2 a token, all held,
no shared expert; a head tied to the embedding. Float32 on the CPU at the
highest matmul precision (conftest), so the tolerances below are
summation-order noise, not a precision. The fixture's embedding (and so
its head) is drawn thirty times larger than `init_params` draws it (logits
of size 5, not 0.2), its q norm's weight at 4 (not `SEEDED_Q_NORM`), so
that what a position attends decides its logits at these widths, and its
expert bias twenty times larger, so that a bias carried into the routing
weights moves them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import lfm2_moe_serve as family
from deeplearning4j_tpu.generation.decode import Lfm2MoeDecoder
from deeplearning4j_tpu.generation.server import GenerationServer
from deeplearning4j_tpu.models import decoder_common, lfm2_moe

PATTERN = ["conv", "conv", "full_attention", "conv"] * 2
TOY = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    num_dense_layers=2, conv_L_cache=3, conv_bias=False,
    layer_types=PATTERN + ["conv"], routed_scaling_factor=1,
    use_expert_bias=True, norm_topk_prob=True, norm_eps=1e-5,
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    model_type="lfm2_moe", held={"experts": [0, 8]})
#: float32 both sides, different summation orders (an online softmax in
#: tiles against whole rows, a tail shifted a token at a time against a
#: padded sequence's taps, grouped against looped experts): logits of size
#: 5 agree to 2e-6. One precision lower misses it by orders
#: (`test_lower_precision_reference_fails_the_tolerance`)
LOGIT_TOL = 2e-5


def _sharpened(params):
    """Logits of size 5, attention that decides them, and an expert bias
    of 0.2 that the weights would feel (the module's docstring)."""
    def sharper(layer):
        if "q_norm" in layer:
            layer = {**layer, "q_norm": layer["q_norm"] * 4.0
                     / lfm2_moe.SEEDED_Q_NORM}
        if "router_bias" in layer:
            layer = {**layer, "router_bias": layer["router_bias"] * 20.0}
        return layer

    return {**params, "embed": params["embed"] * 30.0,
            "layers": [sharper(layer) for layer in params["layers"]]}


@pytest.fixture(scope="module")
def toy():
    cfg = lfm2_moe.Lfm2MoeConfig.from_dict(TOY)
    return cfg, _sharpened(lfm2_moe.init_params(cfg, jax.random.PRNGKey(7)))


def _reference(params, ids, **kw):
    return family.reference_logits(
        params, jnp.atleast_2d(jnp.asarray(ids)),
        family.reference_sizes(TOY), **kw)


def _ids(seed, *shape):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"], shape).astype(np.int32)


# -- the full forward ---------------------------------------------------------
@pytest.mark.parametrize("impl,t", [("dense", 40), ("dense", 3),
                                    ("pallas", 40), ("pallas", 53)],
                         ids=["dense", "dense_shorter_than_a_period",
                              "kernel", "kernel_ragged_tiles"])
def test_forward_matches_reference(toy, impl, t, monkeypatch):
    """The program's forward (attention through the causal kernel in tiles
    of 16, interpreted) against the reference's."""
    cfg, params = toy
    monkeypatch.setattr(lfm2_moe, "PREFILL_BLOCK", 16)
    ids = _ids(t, 2, t)
    got = jax.jit(lambda p, x: lfm2_moe.forward(cfg, p, x, impl=impl))(
        params, ids)
    want = _reference(params, ids)
    assert float(jnp.abs(want).max()) > 3.0
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("fault", family.FAULTS)
def test_planted_faults_move_the_reference(toy, fault):
    """The reference's planted faults (the controls of the cell's `check()`
    on the chip: `benchmarks/tests/test_rehearsal_shortconv.py`) are not
    the reference: each moves every position but the first by an order
    more than the tolerance (at position 0 the convolution reads the
    token's own z alone, and `C * w2 * B * x` is the same whichever gate is
    which)."""
    _, params = toy
    ids = _ids(5, 40)
    moved = np.abs(_reference(params, ids, fault=fault)
                   - _reference(params, ids)).max(-1)[0]
    assert float(moved[1:].min()) > 10 * LOGIT_TOL


def test_lower_precision_reference_fails_the_tolerance(toy):
    """What `LOGIT_TOL` is worth: the reference one precision down (float8
    weights, activations, tails and cached rows) misses it by orders."""
    _, params = toy
    ids = _ids(5, 1, 40)
    lower = _reference(params, ids, lower=True)
    assert float(jnp.abs(lower - _reference(params, ids)).max()) > 1e-2


def test_attention_decides_these_logits(toy):
    """What the comparisons here are worth: the sequence's first token
    changed moves the logits of EVERY later position, through the two
    attention layers (the convolution reads two rows back alone; the
    planted faults show what the taps are worth)."""
    cfg, params = toy
    ids = _ids(3, 40)
    other = ids.copy()
    other[0] = (ids[0] + 1) % TOY["vocab_size"]
    base = lfm2_moe.forward(cfg, params, ids)
    moved = jnp.abs(base - lfm2_moe.forward(cfg, params, other)).max(-1)
    assert float(moved[3:].min()) > 100 * LOGIT_TOL


def test_the_convolution_is_its_taps_without_bias_or_activation(toy):
    """`shortconv` by hand: `[B | C | x] = u W_in` in that order, `z = B *
    x`, `c_t = w0 z_{t-2} + w1 z_{t-1} + w2 z_t` (z zero before 0), out `(C
    * c) W_out`; the tail is z of positions plen - 2 and plen - 1."""
    cfg, params = toy
    layer = params["layers"][0]
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (9, 64)))
    out, tail = lfm2_moe.shortconv(cfg, layer, jnp.asarray(u), 6)
    bcx = u @ np.asarray(layer["in_proj"])
    b, c, x = bcx[:, :64], bcx[:, 64:128], bcx[:, 128:]
    z = b * x
    w = np.asarray(layer["conv_w"])
    zp = np.concatenate([np.zeros((2, 64)), z])
    conv = w[:, 0] * zp[:-2] + w[:, 1] * zp[1:-1] + w[:, 2] * zp[2:]
    np.testing.assert_allclose(out, (c * conv) @ np.asarray(
        layer["out_proj"]), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(tail, z[4:6], atol=1e-6, rtol=0)
    # a step from that tail is the sequence's position 6
    step, shifted = lfm2_moe.shortconv_step(cfg, layer, jnp.asarray(u[6:7]),
                                            tail[None])
    np.testing.assert_allclose(step[0], out[6], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(shifted[0], z[5:7], atol=1e-6, rtol=0)


def test_the_conv_tails_two_steps():
    """`decoder_common.conv_tail` takes the rows before each sequence's
    `plen` of a left-padded batch (zeros before position 0, padding after
    plen never); `conv_shift` hands the convolution the window ending at
    the new row and drops the oldest."""
    rows = jnp.arange(1, 9, dtype=jnp.float32)[:, None] * jnp.ones((1, 3))
    padded = jnp.stack([jnp.pad(rows, ((2, 0), (0, 0)))] * 3)  # (3, 10, 3)
    tail = decoder_common.conv_tail(padded, jnp.array([1, 2, 6]), 2)
    assert tail[:, :, 0].tolist() == [[0, 1], [1, 2], [5, 6]]
    out, after = decoder_common.conv_shift(
        tail, jnp.full((3, 3), 9.0), lambda w: w.sum(1))
    assert out[:, 0].tolist() == [10, 12, 20]
    assert after[:, :, 0].tolist() == [[1, 9], [2, 9], [6, 9]]


def test_layers_zero_and_one_are_dense_and_layer_two_is_not(toy):
    cfg, params = toy
    assert [cfg.is_dense(li) for li in range(4)] == [True, True, False,
                                                     False]
    assert [cfg.is_attention(li) for li in range(8)] == [
        kind == "full_attention" for kind in PATTERN]
    for layer in params["layers"][:2]:
        assert {"d_gate", "d_up", "d_down", "in_proj"} <= set(layer)
        assert "router" not in layer
    for li, layer in enumerate(params["layers"][2:], 2):
        assert {"router", "router_bias", "w_gate"} <= set(layer)
        assert "d_gate" not in layer and "s_gate" not in layer  # no shared
        assert layer["w_gate"].shape == (8, 64, 32)
        assert ("q" in layer) == cfg.is_attention(li)
        assert ("in_proj" in layer) != cfg.is_attention(li)
    assert "head" not in params                            # tied
    # and both kinds of feed-forward are computed: each moves the logits
    ids = _ids(2, 24)
    base = lfm2_moe.forward(cfg, params, ids)
    for li, name in ((1, "d_down"), (2, "w_down"), (3, "out_proj"),
                     (6, "o")):
        layers = list(params["layers"])
        layers[li] = {**layers[li], name: layers[li][name] * 0}
        assert float(jnp.abs(lfm2_moe.forward(
            cfg, {**params, "layers": layers}, ids) - base).max()) > 1e-3


def test_config_refuses_what_it_cannot_run():
    for key, value in (("conv_bias", True), ("use_expert_bias", False),
                       ("norm_topk_prob", False),
                       ("tie_word_embeddings", False),
                       ("rope_scaling", {"type": "yarn", "factor": 4})):
        with pytest.raises(ValueError, match=key):
            lfm2_moe.Lfm2MoeConfig.from_dict({**TOY, key: value})
    with pytest.raises(ValueError, match="rope_parameters"):
        lfm2_moe.Lfm2MoeConfig.from_dict({**TOY, "rope_parameters": {
            "rope_theta": 1e6, "rope_type": "linear"}})
    for types in (PATTERN[:6], ["conv", "sliding_attention"] * 4):
        with pytest.raises(ValueError, match="layer_types"):
            lfm2_moe.Lfm2MoeConfig.from_dict({**TOY, "layer_types": types})
    with pytest.raises(ValueError, match="experts_held"):
        lfm2_moe.Lfm2MoeConfig.from_dict(TOY, experts_held=(4, 8))
    with pytest.raises(ValueError, match="taps"):
        lfm2_moe.Lfm2MoeConfig.from_dict({**TOY, "conv_L_cache": 1})
    cfg = lfm2_moe.Lfm2MoeConfig.from_dict(TOY)
    assert (cfg.head_dim, cfg.kv_width, cfg.rope_theta,
            cfg.route_norm_eps) == (16, 32, 1e6, 1e-6)
    assert cfg.layer_types == tuple(PATTERN)        # the layers held


def test_rotary_turns_halves(toy):
    """Lane j turns with lane j + D/2 by `t theta^(-2j/D)`; position 0 is
    the identity."""
    cfg, _ = toy
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 16))
    pos = jnp.array([0, 1, 7, 100, 16000])
    got = np.asarray(lfm2_moe.rotate(x, lfm2_moe.rope_tables(cfg, pos)))
    want = np.zeros_like(got)
    for j in range(8):
        ang = np.asarray(pos, np.float64) * 1e6 ** (-2 * j / 16)
        a, b = np.asarray(x[..., j]), np.asarray(x[..., j + 8])
        want[..., j] = a * np.cos(ang)[:, None] - b * np.sin(ang)[:, None]
        want[..., j + 8] = b * np.cos(ang)[:, None] \
            + a * np.sin(ang)[:, None]
    np.testing.assert_allclose(got, want, atol=2e-3)      # float32 angles
    np.testing.assert_array_equal(got[0], np.asarray(x[0]))


# -- through the decoder's cache ----------------------------------------------
def _serve_by_hand(dec, prompts, slots, bucket, rung, steps, grow_at=None,
                   grown=None):
    """Prefill `prompts` into `slots` of a 4-slot cache (padded to
    `bucket`), then `steps` greedy decode steps at different positions,
    growing the rung once; every logit vector served, a slot at a time."""
    margs = dec.model_args()
    prefill, step = jax.jit(dec.prefill), jax.jit(dec.step)
    cache = dec.init_cache(4, rung)
    seqs, tokens, got = {}, np.zeros(4, np.int32), {s: [] for s in slots}
    pos = np.zeros(4, np.int32)
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
    """Slots 3, 0 and 2 of a 4-slot cache take prompts of 1 and 2 (tails
    partly zero) and 13 (inside a bucket of 16: its last three rows are
    padding), and decode 10 greedy tokens at DIFFERENT positions while slot
    1 idles; the rung grows from 24 to 48 after the fourth step (the
    attention leaves; the tails stay). Every step's logits are the
    reference's full forward at that position (logits, not tokens)."""
    cfg, params = toy
    monkeypatch.setattr(lfm2_moe, "PREFILL_BLOCK", 16)
    dec = Lfm2MoeDecoder(cfg, params, attn_impl=attn_impl)
    slots, prompts = (3, 0, 2), (_ids(1, 1), _ids(2, 2), _ids(4, 13))
    cache = dec.init_cache(4, 24)
    assert [l.shape for l in cache["k"]] == [(4, 24, 32)] * 2
    assert [l.shape for l in cache["conv"]] == [(4, 2, 64)] * 6
    cache, seqs, got = _serve_by_hand(dec, prompts, slots, 16, 24, 10,
                                      grow_at=4, grown=48)
    assert [l.shape for l in cache["v"]] == [(4, 48, 32)] * 2
    assert [l.shape for l in cache["conv"]] == [(4, 2, 64)] * 6
    for slot, prompt in zip(slots, prompts):
        # causal: one full forward gives every step's reference
        ref = _reference(params, np.array(seqs[slot]))[0]
        np.testing.assert_allclose(np.stack(got[slot]),
                                   ref[len(prompt) - 1:], atol=LOGIT_TOL,
                                   rtol=0)
    counts = dict(zip(dec.counter_names, np.asarray(cache["counts"])))
    # 10 steps x 6 EXPERT layers x 4 slots x 2 choices, all held
    assert counts["moe_pairs"] == 10 * 6 * 4 * 2
    # rows in use a step on each of the 2 attention layers: pos + 1; slots
    # 3, 0, 2 at 1.., 2.., 13.., slot 1 idle at 0
    rows = sum(sum(p + 1 for p in range(start, start + 10))
               for start in (1, 2, 13)) + 10
    assert counts["global_rows_attended"] == 2 * rows


def test_the_tail_holds_the_last_real_positions(toy):
    """After a prefill of 13 real positions in a bucket of 16, a conv
    layer's tail holds z of positions 11 and 12 (NOT the bucket's last
    rows, which are padding), the attention leaves rows 0..12 as the
    prompt's own; a prompt of 1 leaves (0, z_0) and one of 2 (z_0, z_1).
    The other slots are untouched."""
    cfg, params = toy
    dec = Lfm2MoeDecoder(cfg, params, attn_impl="dense")
    prefill = jax.jit(dec.prefill)
    for plen in (13, 1, 2):
        padded = np.zeros(16, np.int32)
        padded[:plen] = _ids(4, 13)[:plen]
        cache, _ = prefill(dec.model_args(), dec.init_cache(2, 24),
                           np.int32(1), padded, np.int32(plen))
        x = lfm2_moe.embed(cfg, params, jnp.asarray(padded))
        tables = lfm2_moe.rope_tables(cfg, jnp.arange(16))
        zs = []
        for li, layer in enumerate(params["layers"]):
            u = lfm2_moe.rms_norm(x, layer["norm_op"], cfg.norm_eps)
            if not cfg.is_attention(li):
                b, _, xx = np.split(np.asarray(u @ layer["in_proj"]), 3, -1)
                zs.append(b * xx)
            x, state = lfm2_moe.apply_layer(cfg, li, layer, x, tables, plen,
                                            impl="dense")
            if cfg.is_attention(li):
                # the layer's leaf follows the earlier layers that own one
                i = sum("k" in [leaf.name for leaf in own]
                        for own in lfm2_moe.decode_leaves(cfg)[:li])
                np.testing.assert_allclose(
                    cache["k"][i][1][:plen], state[0][:plen], atol=1e-5,
                    rtol=0)
        for i, z in enumerate(zs):
            want = np.concatenate([np.zeros((2, 64)), z[:plen]])[-2:]
            np.testing.assert_allclose(cache["conv"][i][1], want, atol=1e-5,
                                       rtol=0)
            assert not np.asarray(cache["conv"][i][0]).any()
        assert not np.asarray(cache["k"][0][0]).any()


def test_grow_pads_the_attention_leaves_and_keeps_the_tails(toy):
    cfg, params = toy
    dec = Lfm2MoeDecoder(cfg, params)
    cache = jax.tree_util.tree_map(
        lambda l: jnp.arange(l.size, dtype=jnp.float32).reshape(
            l.shape).astype(l.dtype), dec.init_cache(2, 8))
    grown = dec.grow(cache, 24)
    for old, new in zip(cache["k"] + cache["v"], grown["k"] + grown["v"]):
        assert new.shape == (2, 24, 32)
        np.testing.assert_array_equal(new[:, :8], old)
        assert not np.asarray(new[:, 8:]).any()
    assert all(a is b for a, b in zip(grown["conv"], cache["conv"]))
    assert grown["counts"] is cache["counts"]
    assert dec.uses_cache_rungs and not dec.supports_draft
    with pytest.raises(ValueError, match="attn_impl"):
        Lfm2MoeDecoder(cfg, params, attn_impl="flash")


# -- through the server -------------------------------------------------------
def test_server_streams_equal_the_decoders_own_and_never_compile(toy):
    """Greedy streams through `GenerationServer` (two requests at once, a
    rung grown mid-service) are what the decoder's own prefill and steps
    give, token for token; past warm-up nothing traces or compiles."""
    cfg, params = toy
    dec = Lfm2MoeDecoder(cfg, params)
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
    assert st["decoder"] == "Lfm2MoeDecoder" and st["state"] == "serving"
    # 6 expert layers x 2 slots x 2 choices a step
    assert st["moe_pairs"] == 6 * 2 * 2 * st["steps"] > 0
    assert 0 < st["global_rows_attended"] <= 2 * 2 * 64 * st["steps"]


# -- the expert layer ---------------------------------------------------------
def test_the_bias_changes_the_choice_and_not_the_weights(toy):
    """The expert bias picks the experts, the sigmoid scores of the picked
    weigh them, over their sum + 1e-6. A bias of +1 on experts 6 and 7
    makes them every token's choice; the layer adds no shared expert."""
    cfg, params = toy
    layer = params["layers"][2]
    g = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.hidden_size))
    bias = jnp.zeros((8,)).at[6:].set(1.0)
    score = jax.nn.sigmoid(g @ layer["router"])
    plain = jnp.argsort(-(score + layer["router_bias"]), -1)[:, :2]
    assert len(np.unique(plain)) > 2
    got, counts = decoder_common.moe(cfg, {**layer, "router_bias": bias}, g)
    val = score[:, 6:]
    wts = val / (val.sum(-1, keepdims=True) + 1e-6)
    want = sum(wts[:, j:j + 1] * decoder_common.swiglu(
        g, layer["w_gate"][6 + j], layer["w_up"][6 + j],
        layer["w_down"][6 + j]) for j in range(2))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert counts.tolist() == [24 * 2, 2, 24]
    # weights from s + b would differ by far more than the tolerance
    biased = (val + 1.0) / (val + 1.0).sum(-1, keepdims=True)
    assert float(jnp.abs(biased - wts).max()) > 1e-2


def test_the_expert_layer_without_a_shared_expert_is_the_looped_sum(
        toy, monkeypatch):
    """`decoder_common.moe` with no `s_gate` starts the routed sum from
    zero: every token's output is the sum of its chosen experts' SwiGLUs,
    weighted, looped here expert by expert; in the ragged path's runs of
    `MOE_CHUNK` tokens too (the toy widths take that path on any backend;
    the kernel's own runs are `tests/test_grouped_matmul.py`'s). Every pair
    is computed on this chip (all 8 held)."""
    cfg, params = toy
    layer = params["layers"][4]
    g = jax.random.normal(jax.random.PRNGKey(8), (48, cfg.hidden_size))
    score = jax.nn.sigmoid(g @ layer["router"])
    idx = jnp.argsort(-(score + layer["router_bias"]), -1,
                      stable=True)[:, :2]
    val = jnp.take_along_axis(score, idx, -1)
    wts = val / (val.sum(-1, keepdims=True) + 1e-6)
    want = jnp.zeros_like(g)
    for e in range(8):
        w_tok = jnp.sum(jnp.where(idx == e, wts, 0.0), -1)
        want = want + w_tok[:, None] * decoder_common.swiglu(
            g, layer["w_gate"][e], layer["w_up"][e], layer["w_down"][e])
    got, counts = decoder_common.moe(cfg, layer, g)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert int(counts[0]) == 48 * 2
    monkeypatch.setattr(decoder_common, "MOE_CHUNK", 16)
    runs, run_counts = decoder_common.moe(cfg, layer, g)
    np.testing.assert_allclose(runs, want, atol=1e-5, rtol=0)
    assert int(run_counts[0]) == 48 * 2


def test_status_says_how_often_an_admits_expert_layer_reads_its_experts(
        monkeypatch):
    """`GenerationServer.status()["expert_runs"]`, at the cell's widths
    and buckets: a TPU's kernel takes 4096 tokens of 2048 a call beside
    the 64 gated experts' blocks, so an expert layer reads its experts
    twice in an admit of 8192 and 4 times in one of 16384 (4 and 8 times
    in runs of `MOE_CHUNK`, the ragged path's, which the CPU takes)."""
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmarks", "configs",
                           "lfm2_24b_a2b_pp5.json")) as f:
        cfg = family.model_config({**json.load(f), "num_hidden_layers": 3},
                                  "bfloat16")
    params = jax.eval_shape(lambda k: lfm2_moe.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dec = Lfm2MoeDecoder(cfg, params)

    def expert_runs():
        srv = GenerationServer(dec, slots=48, cache_lengths=[18432],
                               prompt_buckets=[8192, 16384])
        try:
            return srv.status()["expert_runs"]
        finally:
            srv.shutdown()
    assert expert_runs() == {8192: 4, 16384: 8}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert expert_runs() == {8192: 2, 16384: 4}
