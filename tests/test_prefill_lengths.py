"""The causal prefill kernel over the tile pairs up to each prompt's real
length (`flash_attention(..., lengths=)`), and the three decoders whose
admits hand it their prompt's length (`MLADecoder`, `AfmoeDecoder`,
`Lfm2MoeDecoder`).

The kernel, interpreted, float32 and bfloat16: every row of a q tile that
holds a row below its example's length is the call without `lengths`, to
the bit (the same tiles in the same order with the same arithmetic); the
rows of the q tiles wholly past it are exact zeros, and NaN in the q, k and
v rows past the length's tile reaches no output. The decoders, at the toy
widths of their own test files (float32): the admit's logits at plen - 1,
the grafted rows below plen and ten decode steps after it are what the
bucket-wide kernel gives, for prompts at a tile's edges."""
import functools
import importlib
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import monitoring as mon
from deeplearning4j_tpu.generation.decode import (AfmoeDecoder, Lfm2MoeDecoder,
                                                  MLADecoder)
from deeplearning4j_tpu.generation.server import GenerationServer
from deeplearning4j_tpu.models import afmoe, deepseek_v3, lfm2_moe

fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

T, BLOCK, WINDOW = 64, 16, 20


def _qkv(b, dv, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k = (jax.random.normal(kk, (b, 2, T, 16)).astype(dtype)
            for kk in keys[:2])
    return q, k, jax.random.normal(keys[2], (b, 2, T, dv)).astype(dtype)


def _call(q, k, v, native, window, lengths=None):
    return fa.flash_attention(q, k, v, causal=True, block_q=BLOCK,
                              block_k=BLOCK, native=native, window=window,
                              lengths=lengths)


@pytest.mark.parametrize("lengths", [[1], [BLOCK - 1], [BLOCK], [BLOCK + 1],
                                     [T], [BLOCK + 1, T], [T, 1]],
                         ids=["one", "block_less_one", "block",
                              "block_plus_one", "whole", "two_examples",
                              "two_examples_longer_first"])
@pytest.mark.parametrize("window", [None, WINDOW], ids=["causal", "band"])
@pytest.mark.parametrize("native,dv,dtype", [
    (False, 16, jnp.float32), (True, 8, jnp.float32),
    (True, 16, jnp.bfloat16)], ids=["float32", "native_values_narrower",
                                    "native_bfloat16"])
def test_rows_in_use_are_the_whole_calls_and_the_rest_are_zeros(
        lengths, window, native, dv, dtype):
    """Rows of the q tiles in use are the call without `lengths` to the
    bit, even with NaN in every q, k and v row past the last tile in use;
    the rows of the q tiles past it are exact zeros. Two examples of
    different lengths share one grid: the shorter one's steps past its own
    count do nothing."""
    q, k, v = _qkv(len(lengths), dv, dtype, seed=len(lengths) + dv)
    whole = np.asarray(_call(q, k, v, native, window).astype(jnp.float32))
    ends = [-(-n // BLOCK) * BLOCK for n in lengths]
    for i, end in enumerate(ends):
        q, k, v = (a.at[i, :, end:].set(jnp.nan) for a in (q, k, v))
    got = np.asarray(_call(q, k, v, native, window,
                           jnp.asarray(lengths, jnp.int32)
                           ).astype(jnp.float32))
    assert np.isfinite(got).all()
    for i, end in enumerate(ends):
        np.testing.assert_array_equal(got[i, :, :end], whole[i, :, :end])
        assert not got[i, :, end:].any()


@pytest.mark.parametrize("window", [None, WINDOW, 1, 3 * T],
                         ids=["causal", "band", "own_row", "band_past_all"])
def test_the_step_table_runs_the_pairs_in_use_and_one_step_a_tile_past(
        window):
    """For every length of a 64-row sequence in tiles of 16: the pairs the
    table runs are `n(n + 1) / 2` for the n q tiles in use (causal), or the
    sum of their bands; each is its q tile's k tiles in order, first and
    last marked; one `_ZERO` step follows for each q tile past them; and
    the grid's extent is what `prefill_grid_steps` reckons on the host."""
    q_tiles = T // BLOCK
    spans = fa._row_spans(q_tiles, BLOCK, BLOCK, q_tiles, window)
    lengths = np.arange(1, T + 1, dtype=np.int32)
    q_out, q_in, k_tile, kind, extent = fa._pairs_in_use(
        jnp.asarray(lengths), BLOCK, spans)
    steps = len(q_out) // len(lengths)
    q_out, q_in, k_tile, kind = (np.asarray(a).reshape(len(lengths), steps)
                                 for a in (q_out, q_in, k_tile, kind))
    assert int(extent) == max(fa.prefill_grid_steps(n, T, BLOCK, BLOCK,
                                                    window)[0]
                              for n in lengths)
    for row, n in enumerate(lengths):
        used = -(-n // BLOCK)
        want = [(i, j) for i in range(used)
                for j in range(spans[i][0], spans[i][1] + 1)]
        if window is None:
            assert len(want) == used * (used + 1) // 2
        runs = kind[row] & fa._RUN != 0
        assert list(zip(q_out[row][runs], k_tile[row][runs])) == want
        assert (q_in[row][runs] == q_out[row][runs]).all()
        firsts = [(i, j) for i, j in want if j == spans[i][0]]
        lasts = [(i, j) for i, j in want if j == spans[i][1]]
        assert list(zip(q_out[row][kind[row] & fa._FIRST != 0],
                        k_tile[row][kind[row] & fa._FIRST != 0])) == firsts
        assert list(zip(q_out[row][kind[row] & fa._LAST != 0],
                        k_tile[row][kind[row] & fa._LAST != 0])) == lasts
        zeros = kind[row] & fa._ZERO != 0
        assert list(q_out[row][zeros]) == list(range(used, q_tiles))
        # a zero step names the blocks already resident: nothing fetched
        assert (q_in[row][zeros] == used - 1).all()
        assert (k_tile[row][zeros] == spans[used - 1][1]).all()
        run, static = fa.prefill_grid_steps(n, T, BLOCK, BLOCK, window)
        assert run == len(want) + q_tiles - used
        assert static == q_tiles * (q_tiles if window is None else
                                    fa.band_tiles(q_tiles, BLOCK, BLOCK,
                                                  window, q_tiles))


def test_lengths_refuses_what_it_cannot_run():
    q, k, v = _qkv(1, 16, jnp.float32)
    n = jnp.asarray([5], jnp.int32)
    for kw in (dict(causal=False), dict(causal=True, mask=jnp.ones((1, T)))):
        with pytest.raises(ValueError, match="lengths"):
            fa.flash_attention(q, k, v, lengths=n, **kw)
    with pytest.raises(ValueError, match="lengths"):
        fa.flash_attention(q, k, v, causal=True, lengths=jnp.asarray([5, 6]))
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: fa.flash_attention(
            q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK,
            lengths=n).sum())(q)


# -- the decoders ---------------------------------------------------------
def _sibling(name):
    """A test module of this directory, for its toy widths and weights."""
    spec = importlib.util.spec_from_file_location(
        f"_prefill_lengths_{name}",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


#: decoder -> its test module, class, model module and config class
DECODERS = {
    "kanana2": ("test_deepseek_v3", MLADecoder, deepseek_v3,
                "DeepseekV3Config"),
    "trinity": ("test_afmoe", AfmoeDecoder, afmoe, "AfmoeConfig"),
    "lfm2": ("test_lfm2_moe", Lfm2MoeDecoder, lfm2_moe, "Lfm2MoeConfig"),
}
BUCKET, RUNG, STEPS = 48, 64, 10


@functools.lru_cache(maxsize=None)
def _toy(name):
    test_module, decoder, model, config = DECODERS[name]
    toy = _sibling(test_module)
    cfg = getattr(model, config).from_dict(toy.TOY)
    params = toy._sharpened(model.init_params(cfg, jax.random.PRNGKey(7)))
    return decoder(cfg, params, attn_impl="pallas"), toy


def _bucket_wide(*args, lengths=None, **kw):
    return fa.flash_attention(*args, **kw)


@functools.lru_cache(maxsize=None)
def _programs(name):
    """The admit with the length-aware kernel, the admit with the bucket's
    whole grid (the kernel called without `lengths`), and a decode step;
    traced once a decoder, `plen` an operand."""
    dec, _ = _toy(name)
    model = DECODERS[name][2]
    with pytest.MonkeyPatch.context() as m:
        m.setattr(model, "PREFILL_BLOCK", BLOCK)
        args = (dec.model_args(), dec.init_cache(3, RUNG), np.int32(1),
                np.zeros(BUCKET, np.int32), np.int32(1))
        aware = jax.jit(dec.prefill).lower(*args).compile()
        m.setattr(model, "flash_attention", _bucket_wide)
        whole = jax.jit(dec.prefill).lower(*args).compile()
    return aware, whole, jax.jit(dec.step)


def _real_rows(name, cache, plen):
    """(leaf, rows) pairs of slot 1 that hold real positions after an
    admit of `plen`: a latent leaf's rows of two real positions, a ring
    whole, a full leaf's rows below plen, a conv tail whole."""
    if name == "kanana2":
        return [(leaf[1], plen // 2) for leaf in cache["kv"]]
    dec, _ = _toy(name)
    out = []
    for key in ("k", "v", "conv"):
        for i, leaf in enumerate(cache.get(key, ())):
            ring = key == "conv" or (name == "trinity"
                                     and dec.cfg.is_sliding(i))
            out.append((leaf[1], leaf.shape[1] if ring else plen))
    return out


@pytest.mark.parametrize("plen", [BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK + 1])
@pytest.mark.parametrize("name", list(DECODERS))
def test_the_admit_is_the_bucket_wide_kernels_and_so_are_ten_steps(name,
                                                                   plen):
    """An admit of `plen` real tokens in a bucket of 48, tiles of 16: the
    logits at plen - 1 and every grafted row of a real position are the
    bits the bucket-wide kernel gives, and so are the logits of ten greedy
    steps after it (the same tokens fed to both caches)."""
    dec, toy = _toy(name)
    aware, whole, step = _programs(name)
    margs = dec.model_args()
    prompt = np.zeros(BUCKET, np.int32)
    prompt[:plen] = toy._ids(plen, plen)
    caches, logits = [], []
    for admit in (aware, whole):
        cache, first = admit(margs, dec.init_cache(3, RUNG), np.int32(1),
                             prompt, np.int32(plen))
        caches.append(cache)
        logits.append([np.asarray(first)])
    np.testing.assert_array_equal(*(l[0] for l in logits))
    if -(-plen // BLOCK) < BUCKET // BLOCK:
        # the bucket-wide kernel filled the padding's rows otherwise
        assert not all(np.array_equal(a, b) for a, b in zip(
            *map(jax.tree_util.tree_leaves, caches)))
    for (got, rows), (want, _) in zip(_real_rows(name, caches[0], plen),
                                      _real_rows(name, caches[1], plen)):
        np.testing.assert_array_equal(np.asarray(got[:rows]),
                                      np.asarray(want[:rows]))
    tokens = np.zeros(3, np.int32)
    pos = np.zeros(3, np.int32)
    tokens[1], pos[1] = int(np.argmax(logits[0][0])), plen
    for _ in range(STEPS):
        out = []
        for i, cache in enumerate(caches):
            out.append(step(margs, cache, tokens.copy(), pos.copy()))
            caches[i] = out[-1][1]
        np.testing.assert_array_equal(np.asarray(out[0][0][1]),
                                      np.asarray(out[1][0][1]))
        tokens[1], pos[1] = int(np.argmax(out[0][0][1])), pos[1] + 1


def test_status_counts_the_admits_grid_steps_by_hand(monkeypatch):
    """`status()["prefill_attn_pairs"]` sums, over the admits, one head's
    grid steps in each of LFM2's 2 attention layers: run and static. In
    tiles of 16, a prompt of 10 in the bucket of 24 (2 q tiles) runs pair
    (0, 0) and a zero step for tile 1, where the static grid holds 2 x 2;
    one of 30 in the bucket of 40 (3 q tiles) runs (0, 0), (1, 0), (1, 1)
    and a zero step, where the grid holds 3 x 3. The `serve.admit` span
    carries each admit's pair. Where the prefill takes the dense path the
    decoder reports nothing."""
    monkeypatch.setattr(lfm2_moe, "PREFILL_BLOCK", BLOCK)
    dec, toy = _toy("lfm2")
    assert Lfm2MoeDecoder(dec.cfg, dec.params, attn_impl="dense") \
        .prefill_attn_pairs(10, 24) is None
    layers = 2
    want = [(layers * (1 + 1), layers * 2 * 2),
            (layers * (3 + 1), layers * 3 * 3)]
    assert [dec.prefill_attn_pairs(10, 24),
            dec.prefill_attn_pairs(30, 40)] == want
    srv = GenerationServer(dec, slots=2, cache_lengths=[64],
                           prompt_buckets=[24, 40], method="greedy",
                           max_new_tokens=2, seed=0)
    mon.disable()
    mon.get_tracer().clear()
    try:
        srv.warmup()
        assert srv.status()["prefill_attn_pairs"] == {"run": 0, "static": 0}
        mon.enable()
        for n in (10, 30):
            srv.submit(toy._ids(n, n), max_new_tokens=2).result(timeout=300)
        st = srv.status()
    finally:
        srv.shutdown()
        mon.disable()
    admits = [e["args"] for e in mon.get_tracer().events()
              if e["name"] == "serve.admit"]
    mon.get_tracer().clear()
    assert st["prefill_attn_pairs"] == {"run": sum(r for r, _ in want),
                                        "static": sum(s for _, s in want)}
    assert [(a["attn_pairs"], a["attn_pairs_static"]) for a in admits] \
        == want
