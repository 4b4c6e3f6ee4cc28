"""The Keye-VL-2.0 language model (models/keye_vl.py, KeyeDecoder, the exact
selection of kernels/selection.py, the gated expert layer, the indexer and
selected-attention kernels) against the plain reference that the benchmark
keeps (benchmarks/families/keye_vl_serve.py: full rows of scores, the k best
by a sort, looped experts).

Toy widths that keep every ratio of the published model: hidden 64, 4 query
heads over 2 KV heads of 16, an indexer of 4 heads of 8 over one key head
that keeps `topk` 16 rows a query (contexts here are 24-80, so selection
bites), rotary streams of 2 | 3 | 3 frequencies, 16 experts of width 48 with
4 a token. Float32 on the CPU at the highest matmul precision (conftest), so
the tolerances below are summation-order noise, not a precision."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import keye_vl_serve as family
from deeplearning4j_tpu.generation.decode import KeyeDecoder
from deeplearning4j_tpu.generation.server import GenerationServer
from deeplearning4j_tpu.kernels import indexer
from deeplearning4j_tpu.kernels.selection import (compact_indices,
                                                  top_k_mask)
from deeplearning4j_tpu.models import keye_vl as kv
from deeplearning4j_tpu.parallel.moe import routed_experts

fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

TOY = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=48,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e7,
    rope_scaling={"mrope_section": [2, 3, 3]},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "topk": 16},
    held={"experts": [0, 16]})
#: float32 both sides, different summation orders (tiles and an online
#: softmax against whole rows, grouped against looped experts): logits of
#: size 0.6 agree to 2e-7. A lower precision fails it by orders: bfloat16
#: index sums alone move these logits by 1e-3 (`test_lower_precision_...`)
LOGIT_TOL = 5e-6


@pytest.fixture(scope="module")
def toy():
    cfg = kv.KeyeVLConfig.from_dict(TOY)
    return cfg, kv.init_params(cfg, jax.random.PRNGKey(7))


def _reference(params, ids, sizes=None, **kw):
    return family.reference_logits(
        params, jnp.atleast_2d(jnp.asarray(ids)),
        sizes or family.reference_sizes(TOY), **kw)


def _ids(seed, *shape):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"], shape).astype(np.int32)


# -- the full forward ---------------------------------------------------------
@pytest.mark.parametrize("impl,t,q_block", [
    ("dense", 40, 4096), ("dense", 57, 16), ("pallas", 40, 16),
    ("pallas", 80, 32)],
    ids=["dense", "dense_query_blocks", "kernels", "kernels_longer"])
def test_forward_matches_reference(toy, impl, t, q_block):
    """Contexts of 40-80 against `topk` 16: most rows are NOT attended. The
    query blocks of 16 and 32 split the selection as the 4096-row blocks
    split a prompt of 16384."""
    cfg, params = toy
    ids = _ids(t, 2, t)
    got = jax.jit(lambda p, x: kv.forward(cfg, p, x, impl=impl,
                                          q_block=q_block))(params, ids)
    np.testing.assert_allclose(got, _reference(params, ids), atol=LOGIT_TOL,
                               rtol=0)


def test_selection_changes_the_logits(toy):
    """The test above would pass a model that attends every row only if
    selection changed nothing: with `topk` past the context it does."""
    cfg, params = toy
    ids = _ids(3, 1, 40)
    dense_cfg = kv.KeyeVLConfig.from_dict(
        {**TOY, "sa_config": {**TOY["sa_config"], "topk": 64}})
    sparse = kv.forward(cfg, params, ids)
    full = kv.forward(dense_cfg, params, ids)
    np.testing.assert_allclose(sparse[:, :16], full[:, :16], atol=LOGIT_TOL)
    assert float(jnp.abs(sparse[:, 24:] - full[:, 24:]).max()) > 1e-3


def test_lower_precision_reference_fails_the_tolerance(toy):
    """What `LOGIT_TOL` is worth: the reference one precision down (float8
    weights, bfloat16 index sums) misses it by four orders."""
    cfg, params = toy
    ids = _ids(5, 1, 40)
    lower = _reference(params, ids, lower=True)
    assert float(jnp.abs(lower - _reference(params, ids)).max()) > 1e-2


def test_three_unequal_position_streams_match_the_reference(toy):
    """M-RoPE: temporal, height and width positions that differ (an image
    tile's would) turn their own sections of the 8 frequencies."""
    cfg, params = toy
    t = 32
    ids = _ids(9, 1, t)
    rng = np.random.default_rng(2)
    positions = np.stack([np.arange(t), rng.integers(0, 40, t),
                          rng.integers(0, 40, t)]).astype(np.int32)
    got = kv.forward(cfg, params, ids, jnp.asarray(positions))
    want = _reference(params, ids, positions=jnp.asarray(positions))
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    # and the streams matter: text positions give other logits
    assert float(jnp.abs(got - kv.forward(cfg, params, ids)).max()) > 1e-4


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(ValueError, match="mrope_section"):
        kv.KeyeVLConfig.from_dict(
            {**TOY, "rope_scaling": {"mrope_section": [2, 2, 2]}})
    with pytest.raises(ValueError, match="experts_held"):
        kv.KeyeVLConfig.from_dict(TOY, experts_held=(12, 8))
    with pytest.raises(ValueError, match="ONE key head"):
        kv.KeyeVLConfig.from_dict(
            {**TOY, "sa_config": {**TOY["sa_config"],
                                  "indexer_num_kv_heads": 2}})
    with pytest.raises(ValueError, match="renormalised"):
        kv.KeyeVLConfig.from_dict({**TOY, "norm_topk_prob": False})


# -- exact selection without a sort -------------------------------------------
@pytest.mark.parametrize("s,c,width", [(3, 200, 16), (4, 384, 128),
                                       (2, 18432, 2048)],
                         ids=["ragged_blocks", "whole_blocks", "the_rung"])
def test_selected_set_equals_top_k_on_distinct_scores(s, c, width):
    rng = np.random.default_rng(c)
    x = jnp.asarray(rng.permutation(s * c).reshape(s, c), jnp.float32)
    in_use = rng.integers(width, c, s)
    x = jnp.where(jnp.arange(c)[None, :] < in_use[:, None], x, -jnp.inf)
    k = jnp.asarray(rng.integers(1, width + 1, s), jnp.int32)
    mask = jax.jit(top_k_mask)(x, k)
    rows = jax.jit(lambda m: compact_indices(m, width))(mask)
    _, want = jax.lax.top_k(x, width)
    for i in range(s):
        ki = int(k[i])
        assert int(mask[i].sum()) == ki
        assert set(np.flatnonzero(mask[i])) == set(np.asarray(want[i, :ki]))
        np.testing.assert_array_equal(rows[i, :ki], np.flatnonzero(mask[i]))
        assert (np.asarray(rows[i]) < c).all()


def test_ties_at_the_threshold_go_to_the_lower_index():
    """ReLU makes exact ties possible; `lax.top_k` (and the reference's
    stable sort) keep the lower positions."""
    x = jnp.asarray([[1., 0., 2., 0., 0., 3., 0., -1.],
                     [5., 5., 5., 5., 5., 5., 5., 5.]])
    mask = top_k_mask(x, jnp.asarray([5, 3], jnp.int32))
    np.testing.assert_array_equal(
        mask, [[1, 1, 1, 1, 0, 1, 0, 0], [1, 1, 1, 0, 0, 0, 0, 0]])
    rows = compact_indices(mask, 5)
    np.testing.assert_array_equal(rows[0], [0, 1, 2, 3, 5])
    np.testing.assert_array_equal(rows[1, :3], [0, 1, 2])


# -- the indexer and selected-attention kernels (interpret mode) --------------
@pytest.mark.parametrize("tq,tk,offset", [(48, 80, 32), (16, 16, 0),
                                          (40, 40, 0)])
def test_index_score_kernel_matches_its_equation(tq, tk, offset):
    rng = np.random.default_rng(tq)
    q = jnp.asarray(rng.normal(size=(4, tq, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(tk, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(tq, 4)), jnp.float32)
    want = indexer.index_scores_dense(q, k, w, 0.25, offset)
    got = indexer.index_scores(q, k, w, 0.25, offset, impl="pallas",
                               block_q=16, block_k=32, interpret=True)
    assert np.isneginf(np.asarray(want)).any()      # the causal corner
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_decode_index_kernel_reads_the_packed_leaf():
    """Two positions a row: the kernel's even and odd scores come back in
    the positions' order, -inf past each slot's position; a tile wholly
    past it is never computed."""
    rng = np.random.default_rng(0)
    s, c, d = 3, 64, 8
    rows = jnp.asarray(rng.normal(size=(s, c, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(s, 4, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(s, 4)), jnp.float32)
    pos = jnp.asarray([63, 0, 21], jnp.int32)
    packed = indexer.pack_rows(rows)
    assert packed.shape == (s, c // 2, 2 * d)
    np.testing.assert_array_equal(indexer.unpack_rows(packed), rows)
    want = indexer.index_scores_decode(q, packed, w, pos, 0.5, impl="dense")
    got = indexer.index_scores_decode(q, packed, w, pos, 0.5, impl="pallas",
                                      block_k=8, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.isfinite(np.asarray(got[2, :22])).all() \
        and np.isneginf(np.asarray(got[2, 22:])).all()
    # a row write sets one half of a packed row and keeps the other
    new = jnp.asarray(rng.normal(size=(s, d)), jnp.float32)
    at = jnp.asarray([5, 8, 64], jnp.int32)         # the last one: past it
    wrote = indexer.unpack_rows(indexer.write_packed_row(packed, at, new))
    want_rows = np.array(rows)
    want_rows[0, 5], want_rows[1, 8] = new[0], new[1]
    np.testing.assert_array_equal(wrote, want_rows)


@pytest.mark.parametrize("tq,tk,offset,hq,hkv,d", [
    (32, 80, 48, 4, 2, 16), (40, 40, 0, 8, 1, 8)])
def test_selected_attention_kernel_matches_masked_softmax(tq, tk, offset,
                                                          hq, hkv, d):
    rng = np.random.default_rng(tk)
    q = jnp.asarray(rng.normal(size=(tq, hq * d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(tk, hkv * d)), jnp.float32)
            for _ in range(2))
    seen = np.arange(tk)[None, :] <= offset + np.arange(tq)[:, None]
    sel = seen & (rng.random((tq, tk)) < 0.3)
    sel[np.arange(tq), offset + np.arange(tq)] = True   # a row keeps itself
    want = fa.flash_attention_selected(q, k, v, jnp.asarray(sel), hkv,
                                       impl="dense")
    got = fa.flash_attention_selected(
        q, k, v, jnp.asarray(sel), hkv, q_offset=offset, impl="pallas",
        block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # query head i reads KV head i // group, and only the selected keys
    i, row = hq - 1, tq - 1
    kvh = i // (hq // hkv)
    s = (k[:, kvh * d:(kvh + 1) * d] @ q[row, i * d:(i + 1) * d]) / d ** 0.5
    p = jax.nn.softmax(jnp.where(jnp.asarray(sel[row]), s, -jnp.inf))
    np.testing.assert_allclose(got[row, i * d:(i + 1) * d],
                               p @ v[:, kvh * d:(kvh + 1) * d], atol=2e-6)


def _ragged_case(dtype, hq, hkv, d, c, seed, lengths=None):
    """Slots of a rung, by default four: lengths 1, the whole rung, one
    that no tile divides, and 0; a random selection of the rows in use as
    the mask; and large finite garbage in every row at or past a slot's
    length, set in the mask too, so that only `lengths` hides it."""
    rng = np.random.default_rng(seed)
    lengths = np.array([1, c, c // 2 + 77, 0] if lengths is None
                       else lengths, np.int32)
    slots = len(lengths)
    in_use = np.arange(c)[None, :] < lengths[:, None]
    q = jnp.asarray(rng.normal(size=(slots, hq, d)), dtype)
    k, v = (jnp.asarray(np.where(in_use[..., None], rng.normal(
        size=(slots, c, hkv * d)), 3e4), dtype) for _ in range(2))
    selected = in_use & (rng.random((slots, c)) < 0.3)
    live = np.flatnonzero(lengths)
    selected[live, lengths[live] - 1] = True           # none is empty
    return q, k, v, selected, jnp.asarray(lengths)


@pytest.mark.parametrize("dtype,hq,hkv,d,c,tol", [
    (jnp.bfloat16, 32, 4, 128, 2048, 2e-2),
    (jnp.float32, 4, 4, 16, 1536, 2e-6)],
    ids=["grouped_query_bfloat16", "multi_head_float32"])
def test_decode_kernel_under_lengths_matches_masked_softmax(dtype, hq, hkv,
                                                            d, c, tol):
    """The selection mask over a rung read in place: rows past a slot's
    length are masked whatever the mask says, a tile wholly past it leaves
    no trace of the garbage it holds, and a slot of length 0 gives zeros;
    the dense path means the same."""
    q, k, v, selected, lengths = _ragged_case(dtype, hq, hkv, d, c, c)
    tile = fa.decode_tile_rows(c, hkv * d, dtype)
    assert c % tile == 0 and tile < c and (c // 2 + 77) % tile
    selected = jnp.asarray(selected)
    want = fa._masked_attend(q[:, :, None], k, v,
                             selected[:, None, :])[:, :, 0]
    past = jnp.arange(c)[None, :] >= lengths[:, None]   # the garbage "valid"
    for mask in (selected, selected | past):
        for impl in ("pallas", "dense"):
            got = fa.flash_attention_decode(q, k, v, mask, impl=impl,
                                            interpret=True, lengths=lengths)
            assert np.isfinite(np.asarray(got, np.float32)).all()
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32),
                                       atol=tol, rtol=0)
            assert not np.asarray(got[3], np.float32).any()
    # a tile the caller names, smaller than the rule's
    got = fa.flash_attention_decode(q, k, v, selected, impl="pallas",
                                    interpret=True, lengths=lengths,
                                    block_k=128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=0)
    with pytest.raises(ValueError, match="lengths must be"):
        fa.flash_attention_decode(q, k, v, selected, lengths=lengths[:3])


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_decode_kernel_reads_a_short_rung_in_the_tiles_in_use(dtype, tol,
                                                              monkeypatch):
    """Plain multi-head attention over BERT's kind of rung (512 rows, as
    many cache heads as query heads, every row in use up to the length
    attended): slots that hold nothing, one row, a row short of a tile, a
    tile, a row more, and the rung. The grid is the tiles in use and no
    more; what the garbage past a length holds leaves no trace."""
    hq = hkv = 12
    d, c = 16, 512
    tile = fa.decode_tile_rows(c, hkv * d, dtype)
    assert 128 <= tile < c and c % tile == 0
    lens = [0, 1, tile - 1, tile, tile + 1, c]
    q, k, v, _, lengths = _ragged_case(dtype, hq, hkv, d, c, 38, lens)
    in_use = jnp.arange(c)[None, :] < lengths[:, None]
    want = fa._masked_attend(q[:, :, None], k, v, in_use[:, None, :])[:, :, 0]
    grids = []
    real = fa.pl.pallas_call
    monkeypatch.setattr(fa.pl, "pallas_call", lambda kernel, **kw: (
        grids.append(kw["grid_spec"].grid), real(kernel, **kw))[1])
    everything = jnp.ones((len(lens), c), bool)        # the garbage "valid"
    for mask in (in_use, everything):
        for impl in ("pallas", "dense"):
            got = fa.flash_attention_decode(q, k, v, mask, impl=impl,
                                            interpret=True, lengths=lengths)
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32),
                                       atol=tol, rtol=0)
            assert not np.asarray(got[0], np.float32).any()
    # 1 + 1 + 1 + 1 + 2 tiles and the rung's: a slot that holds nothing
    # still takes one step, which writes its zeros
    assert [int(g[0]) for g in grids] == [6 + c // tile] * 2


def test_tiles_in_use_lays_the_slots_end_to_end():
    """`_tiles_in_use`: a step's slot and tile, slot by slot, each slot its
    rows in use rounded up to a tile and at least one; the steps past the
    extent (never run) name the last slot."""
    slot, tile, used, extent = fa._tiles_in_use(
        jnp.asarray([0, 300, 128, 512, 129], jnp.int32), 128, 4)
    assert int(extent) == 1 + 3 + 1 + 4 + 2 and slot.shape == (20,)
    assert used.tolist() == [1, 3, 1, 4, 2]
    assert slot[:11].tolist() == [0, 1, 1, 1, 2, 3, 3, 3, 3, 4, 4]
    assert tile[:11].tolist() == [0, 0, 1, 2, 0, 0, 1, 2, 3, 0, 1]
    assert set(slot[11:].tolist()) == {4}


@pytest.mark.parametrize("dtype,hq,hkv,d,c", [
    (jnp.bfloat16, 32, 4, 128, 1024), (jnp.float32, 4, 4, 16, 512)],
    ids=["grouped_query_bfloat16", "multi_head_float32"])
def test_decode_kernel_without_lengths_is_the_call_it_was(dtype, hq, hkv, d,
                                                          c, monkeypatch):
    """`lengths=None` builds today's `pallas_call`: a plain grid, no
    scalar-prefetch operand, no VMEM limit, a tile of 512 rows; and gives
    bit for bit what the kernel gives with every length at the rung."""
    q, k, v, selected, _ = _ragged_case(dtype, hq, hkv, d, c, 3)
    selected = jnp.asarray(selected)
    calls = []
    real = fa.pl.pallas_call
    monkeypatch.setattr(fa.pl, "pallas_call", lambda kernel, **kw: (
        calls.append(kw), real(kernel, **kw))[1])
    plain = fa.flash_attention_decode(q, k, v, selected, impl="pallas",
                                      interpret=True)
    ragged = fa.flash_attention_decode(
        q, k, v, selected, impl="pallas", interpret=True, block_k=512,
        lengths=jnp.full((4,), c, jnp.int32))
    was, now = calls
    assert was["grid"] == (4, c // 512) and "grid_spec" not in was
    assert len(was["in_specs"]) == 4
    assert was["compiler_params"].vmem_limit_bytes is None
    # with lengths: one axis over the tiles in use, the step's slot and
    # tile and a slot's tiles scalar-prefetched
    assert now["grid_spec"].num_scalar_prefetch == 3 and "grid" not in now
    assert int(now["grid_spec"].grid[0]) == 4 * (c // 512)
    np.testing.assert_array_equal(np.asarray(plain, np.float32),
                                  np.asarray(ragged, np.float32))


def test_the_rule_reads_in_place_up_to_twelve_times_topk():
    """The benchmark configuration's rung, 18432 = 9 x `topk` 2048, takes
    the in-place read; a rung past the rule keeps the gather. A function
    of the two static shapes alone."""
    assert kv.attends_in_place(18432, 2048)
    assert kv.attends_in_place(12 * 2048, 2048)
    assert not kv.attends_in_place(12 * 2048 + 2, 2048)
    assert not kv.attends_in_place(32768, 2048)
    assert kv.attends_in_place(64, 16) \
        and not kv.attends_in_place(256, 16)
    # and the tile the kernel reads that rung in comes from its shapes
    assert fa.decode_tile_rows(18432, 512, jnp.bfloat16) == 1024
    assert fa.decode_tile_rows(64, 32, jnp.float32) == 64


@pytest.mark.parametrize("rung,lanes,dtype,tile", [
    (18432, 512, jnp.bfloat16, 1024),      # Keye's leaves: 1 MiB of K
    (512, 768, jnp.float32, None),         # BERT-base's: under the rung
    (512, 768, jnp.bfloat16, None),
    (48, 768, jnp.float32, 48),            # rungs no tile divides: whole
    (32, 768, jnp.float32, 32),
    (70, 32, jnp.float32, 70),
    (128, 768, jnp.float32, 128)],         # and one that IS the least tile
    ids=["keye", "bert_float32", "bert_bfloat16", "rung_48", "rung_32",
         "rung_70", "rung_128"])
def test_the_tile_comes_from_the_rung_the_lanes_and_the_dtype(rung, lanes,
                                                              dtype, tile):
    """`decode_tile_rows`: one rule over the call's static shapes. A long
    rung takes the largest tile under 1 MiB of K; a short one a share of
    itself (a slot reads its rows in use rounded UP to a tile: at 127 rows
    in use of 512, a tile of 512 is the whole loss); a rung that no tile
    divides, or that holds too few, is one tile."""
    got = fa.decode_tile_rows(rung, lanes, dtype)
    assert rung % got == 0
    if tile is None:
        assert 128 <= got < rung and got % 128 == 0
    else:
        assert got == tile


# -- the decoder: prefill, then decode through the three leaves ---------------
@pytest.mark.parametrize("rung", [64, 256], ids=["in_place", "gathered"])
@pytest.mark.parametrize("attn_impl", ["dense", "pallas"])
def test_prefill_then_ten_steps_match_the_full_forward(toy, attn_impl, rung):
    """Slots 2 and 0 of a 3-slot cache take prompts of 21 and 35 (buckets
    of 40: both past `topk` 16) and decode 10 greedy tokens at DIFFERENT
    positions while slot 1 idles: every step's logits are the reference's
    full forward at that position, so each step attended the rows the
    reference's sort selects. A rung of 64 = 4 x `topk` reads them where
    they lie, one of 256 = 16 x `topk` gathers them first."""
    cfg, params = toy
    assert kv.attends_in_place(rung, cfg.indexer_topk) == (rung == 64)
    dec = KeyeDecoder(cfg, params, attn_impl=attn_impl)
    margs = dec.model_args()
    prefill, step = jax.jit(dec.prefill), jax.jit(dec.step)
    slots, prompts = (2, 0), (_ids(1, 21), _ids(2, 35))
    cache = dec.init_cache(3, rung)
    assert [l.shape for l in (cache["k"][0], cache["ki"][1])] \
        == [(3, rung, 32), (3, rung // 2, 16)]
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
    for _ in range(10):
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
    # 10 steps x 2 layers x 3 slots x 4 choices, every expert held
    assert counts["moe_pairs"] == 10 * 2 * 3 * 4
    # rows in use: 22..31, 36..45 and the idle slot's 1; kept: 16, 16, 1
    assert counts["dsa_rows_scored"] == 2 * (265 + 405 + 10)
    assert counts["dsa_rows_selected"] == 2 * 10 * (16 + 16 + 1)
    # in place a slot reads its whole one-tile rung; gathered, what it kept
    assert counts["dsa_rows_read"] == (2 * 10 * 3 * 64 if rung == 64
                                       else counts["dsa_rows_selected"])
    # the `(S, topk, Hkv·Dh)` rung of gathered rows exists on one side only
    hlo = step.lower(margs, cache, tokens, pos).as_text()
    assert ("tensor<3x16x32xf32>" in hlo) == (rung == 256)


def test_rows_read_are_the_rows_in_use_rounded_up_to_the_tile(toy):
    """`dsa_rows_read` on a rung the kernel reads in several tiles (4096
    rows of 32 float32 lanes): `ceil(in_use / tile) * tile` a slot a layer,
    so one tile for a slot at position 21 or 0, and what holds row `tile +
    40` for the third. `topk` 512 keeps that rung under the rule."""
    _, params = toy
    cfg = kv.KeyeVLConfig.from_dict(
        {**TOY, "sa_config": {**TOY["sa_config"], "topk": 512}})
    dec = KeyeDecoder(cfg, params, attn_impl="dense")
    tile = fa.decode_tile_rows(4096, cfg.kv_width, jnp.float32)
    assert tile in (512, 1024, 2048)
    cache = dec.init_cache(3, 4096)
    pos = np.array([21, 0, tile + 40], np.int32)
    _, cache = jax.jit(dec.step)(dec.model_args(), cache,
                                 np.ones(3, np.int32), pos)
    counts = dict(zip(dec.counter_names, np.asarray(cache["counts"])))
    assert counts["dsa_rows_scored"] == 2 * (22 + 1 + tile + 41)
    assert counts["dsa_rows_selected"] == 2 * (22 + 1 + 512)
    assert counts["dsa_rows_read"] == 2 * (tile + tile + 2 * tile)


def test_grow_pads_all_three_kinds_of_leaf_together(toy):
    cfg, params = toy
    dec = KeyeDecoder(cfg, params)
    cache = jax.tree_util.tree_map(
        lambda l: jnp.arange(l.size, dtype=jnp.float32).reshape(
            l.shape).astype(l.dtype), dec.init_cache(2, 8))
    grown = dec.grow(cache, 24)
    for name, shape in (("k", (2, 24, 32)), ("v", (2, 24, 32)),
                        ("ki", (2, 12, 16))):
        for old, new in zip(cache[name], grown[name]):
            assert new.shape == shape
            np.testing.assert_array_equal(new[:, :old.shape[1]], old)
            assert not np.asarray(new[:, old.shape[1]:]).any()
    assert grown["counts"] is cache["counts"]
    assert dec.uses_cache_rungs and not dec.supports_draft
    with pytest.raises(ValueError, match="must be even"):
        dec.init_cache(2, 9)


# -- through the server ---------------------------------------------------------
@pytest.mark.parametrize("topk", [16, 2], ids=["in_place", "gathered"])
def test_server_streams_equal_the_decoders_own_and_never_compile(toy, topk):
    """Greedy streams through `GenerationServer` (two requests at once, a
    rung grown mid-service) are what the decoder's own prefill and steps
    give, token for token; past warm-up nothing traces or compiles. With
    `topk` 16 both rungs (32, 64) are read in place, with `topk` 2 (the
    same weights: no shape depends on it) both are past the rule."""
    _, params = toy
    cfg = kv.KeyeVLConfig.from_dict(
        {**TOY, "sa_config": {**TOY["sa_config"], "topk": topk}})
    assert [kv.attends_in_place(r, topk) for r in (32, 64)] \
        == [topk == 16] * 2
    dec = KeyeDecoder(cfg, params)
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
    assert st["decoder"] == "KeyeDecoder" and st["state"] == "serving"
    assert st["moe_pairs"] == 2 * 2 * 4 * st["steps"] > 0
    assert 0 < st["dsa_rows_selected"] <= 2 * 2 * topk * st["steps"]
    assert st["dsa_rows_selected"] < st["dsa_rows_scored"]
    # `status()` shows the sixth counter: in place each slot's one-tile rung
    # a layer a step, gathered what was kept
    if topk == 16:
        assert 2 * 2 * 32 * st["steps"] <= st["dsa_rows_read"] \
            <= 2 * 2 * 64 * st["steps"]
    else:
        assert st["dsa_rows_read"] == st["dsa_rows_selected"]


# -- the gated expert layer ---------------------------------------------------
def _looped_swiglu(x, probs, w_gate, w_up, w_down, first, count, k):
    """The reference's way: every held expert over every token, masked."""
    val, idx = jax.lax.top_k(probs, k)
    wts = val / val.sum(-1, keepdims=True)
    out = jnp.zeros((x.shape[0], w_down.shape[2]))
    for j in range(count):
        w_tok = jnp.where(idx == first + j, wts, 0.0).sum(-1)
        out = out + w_tok[:, None] * (
            (jax.nn.silu(x @ w_gate[j]) * (x @ w_up[j])) @ w_down[j])
    return out


@pytest.mark.parametrize("t,first,count", [(24, 0, 16), (24, 4, 4),
                                           (1, 8, 8), (80, 4, 8)],
                         ids=["all_held", "a_quarter", "one_token",
                              "groups_over_a_tile"])
def test_gated_kernel_path_equals_ragged_path_and_looped_experts(t, first,
                                                                 count):
    """`routed_experts` with a gate stack at widths the kernel takes (128
    in and out, expert width 256; 16 experts, 4 a token): the Pallas path,
    interpreted, the `lax.ragged_dot` path and the loop over experts
    agree."""
    rng = np.random.default_rng(t + first)
    x = jnp.asarray(rng.normal(size=(t, 128)), jnp.float32)
    probs = jax.nn.softmax(jnp.asarray(rng.normal(size=(t, 16)),
                                       jnp.float32))
    w_gate, w_up = (jnp.asarray(rng.normal(size=(count, 128, 256)) * 0.05,
                                jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(count, 256, 128)) * 0.05,
                         jnp.float32)
    args = (x, probs, None, w_up, w_down, (first, count), 4, 1.0,
            jax.nn.silu)
    got, got_counts = routed_experts(*args, impl="pallas", interpret=True,
                                     w_gate=w_gate)
    ragged, ragged_counts = routed_experts(*args, impl="ragged",
                                           w_gate=w_gate)
    want = _looped_swiglu(x, probs, w_gate, w_up, w_down, first, count, 4)
    np.testing.assert_allclose(got, ragged, atol=2e-6, rtol=0)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert list(got_counts) == list(ragged_counts)
    # the gate is not a no-op: without it the layer is another function
    plain, _ = routed_experts(*args, impl="ragged")
    assert float(jnp.abs(plain - ragged).max()) > 1e-3


def test_long_sequences_go_through_the_experts_in_runs(toy, monkeypatch):
    cfg, params = toy
    layer = params["layers"][0]
    g = jax.random.normal(jax.random.PRNGKey(3), (48, 64))
    whole, whole_counts = kv.moe(cfg, layer, g)
    monkeypatch.setattr(kv, "MOE_CHUNK", 16)
    runs, run_counts = kv.moe(cfg, layer, g)
    np.testing.assert_allclose(runs, whole, atol=1e-6)
    assert list(run_counts[:1]) == list(whole_counts[:1]) == [48 * 4]


def test_eight_shares_of_sixteen_add_up_to_the_uncut_reference():
    """The cut, tied to the model: a one-layer model with the published 128
    experts and 8 a token. Eight chips hold 16 experts each; what each
    computes of the expert layer (`moe` with its `experts_held`), added
    up over the eight with the attention block counted once, is the UNCUT
    reference's layer: the logits that follow are the reference's with all
    128 experts held."""
    whole = {**TOY, "num_hidden_layers": 1, "num_experts": 128,
             "num_experts_per_tok": 8, "held": {"experts": [0, 128]}}
    cfg = kv.KeyeVLConfig.from_dict(whole)
    params = kv.init_params(cfg, jax.random.PRNGKey(11))
    layer = params["layers"][0]
    ids = _ids(6, 40)
    tables = kv.rope_tables(cfg, kv.text_positions(40))
    h, _ = kv.attention_block(cfg, layer, params["embed"][ids], tables)
    g = kv.rms_norm(h, layer["norm2"], cfg.rms_norm_eps)
    parts, pairs = [], 0
    for first in range(0, 128, 16):
        share = kv.KeyeVLConfig.from_dict(whole, experts_held=(first, 16))
        held = {**layer, **{name: layer[name][first:first + 16]
                            for name in ("w_gate", "w_up", "w_down")}}
        out, counts = kv.moe(share, held, g)
        parts.append(out)
        pairs += int(counts[0])
    assert pairs == 40 * 8                   # every pair on exactly one chip
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    got = kv.logits(cfg, params, h + sum(parts))
    want = _reference(params, ids, family.reference_sizes(whole))[0]
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    # and one share alone is the reference given that share
    one = {**whole, "num_experts": 16, "held": {"experts": [32, 48]}}
    held = {**layer, **{name: layer[name][32:48]
                        for name in ("w_gate", "w_up", "w_down")}}
    cut = {**params, "layers": [held]}
    got = kv.forward(kv.KeyeVLConfig.from_dict(
        whole, experts_held=(32, 16)), cut, ids)
    np.testing.assert_allclose(
        got, _reference(cut, ids, family.reference_sizes(one))[0],
        atol=LOGIT_TOL, rtol=0)
