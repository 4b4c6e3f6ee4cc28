"""The serving loop hands each program ONE host operand: the admit's
values (slot, prompt length, rng key, method, temperature, top-k, the
padded prompt and, paged, the prefill's write row) packed into one int32
array, and a decode block's eos / budget / draft / page-table columns
into one (S, w) int32 array. The programs see the very bits they had as
separate operands; the on-disk executable store names an entry from its
argument signature too, so a changed signature is a clean miss."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.generation.decode import BertDecoder
from deeplearning4j_tpu.generation.sampling import (SAMPLE, method_id,
                                                    sample_step)
from deeplearning4j_tpu.generation.server import (ADMIT_HEADER,
                                                  GenerationServer,
                                                  pack_admit, unpack_admit)
from deeplearning4j_tpu.models.bert import BertConfig, init_bert_params
from deeplearning4j_tpu.runtime import executables


@pytest.fixture(scope="module")
def bert():
    cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=64)
    return cfg, init_bert_params(cfg, jax.random.PRNGKey(0))


def _server(bert, paged=False, **kw):
    cfg, params = bert
    dkw = dict(page_size=8, pool_pages=24) if paged else {}
    kw.setdefault("slots", 2)
    kw.setdefault("cache_lengths", [32])
    kw.setdefault("prompt_buckets", [8, 16])
    return GenerationServer(BertDecoder(cfg, params, **dkw), **kw)


# -- one operand a program ---------------------------------------------------
@pytest.mark.parametrize("paged,knobs", [
    (False, {"superstep": 2}), (True, {"superstep": 2}),
    (False, {"draft": 2}), (True, {"draft": 2})],
    ids=["superstep-dense", "superstep-paged", "verify-dense",
         "verify-paged"])
def test_each_program_takes_one_operand_past_model_and_state(bert, paged,
                                                             knobs):
    srv = _server(bert, paged=paged, **knobs)
    srv.warmup()
    try:
        n = srv.decoder.n_model_args + 8      # model args, donated state
        block = ("verify", 32, 2) if "draft" in knobs \
            else ("superstep", 32, 2)
        seen = {}
        for key in (block, ("admit", 32, 8), ("admit", 32, 16)):
            args = srv._store.lookup(key).call.args_info[0]
            assert len(args) == n + 1, key
            seen[key] = (tuple(args[-1].shape), args[-1].dtype)
        assert seen[block] == ((2, srv._step_width(32)), jnp.int32)
        pages = 1 if paged else 0
        assert seen[("admit", 32, 8)] == ((ADMIT_HEADER + 8 + pages,),
                                          jnp.int32)
        assert seen[("admit", 32, 16)] \
            == ((ADMIT_HEADER + 16 + 2 * pages,), jnp.int32)
        # and the programs run on it: a greedy and a sampled request
        got = [srv.submit([3, 1, 4, 1, 5], max_new_tokens=5,
                          method=m, temperature=0.8, top_k=4)
               for m in ("greedy", "sample")]
        assert [len(r.result(timeout=120)) for r in got] == [5, 5]
    finally:
        srv.shutdown()


# -- the header's bits --------------------------------------------------------
@pytest.mark.parametrize("wrow", [None, np.array([7, 0], np.int32)],
                         ids=["dense", "paged"])
def test_packed_header_round_trips_key_and_temperature_bit_for_bit(wrow):
    key = np.array([0x80000001, 0xFFFFFFFE], np.uint32)   # top bits set
    prompt = np.array([9, 8, 7, 6, 5], np.int32)
    packed = pack_admit(3, 5, key, SAMPLE, 0.8, 40, prompt, 16, wrow)
    assert packed.dtype == np.int32
    assert packed.shape == (ADMIT_HEADER + 16 + (0 if wrow is None
                                                 else 2),)
    out = jax.jit(lambda p: unpack_admit(p, 16))(packed)
    slot, pr, plen, k, m, t, topk, w = jax.device_get(out)
    assert (int(slot), int(plen), int(m), int(topk)) == (3, 5, SAMPLE, 40)
    assert k.dtype == np.uint32 and k.tolist() == key.tolist()
    assert t.dtype == np.float32
    assert t.view(np.int32) == np.float32(0.8).view(np.int32)
    np.testing.assert_array_equal(pr, np.r_[prompt, np.zeros(11, np.int32)])
    if wrow is None:
        assert w is None
    else:
        np.testing.assert_array_equal(w, wrow)


# -- what an admit samples ----------------------------------------------------
@pytest.mark.parametrize("top_k", [0, 5])
def test_sampled_admit_first_token_is_sample_step_over_prefill(bert,
                                                               top_k):
    srv = _server(bert, seed=11)
    prompt = np.array([5, 9, 2, 33, 17, 8, 1], np.int32)
    try:
        got = srv.generate(prompt, max_new_tokens=1, eos_id=None,
                           method="sample", temperature=0.8, top_k=top_k,
                           timeout=120)
    finally:
        srv.shutdown()
    dec = srv.decoder
    key = srv._admit_key(1)                  # the first admission's id
    padded = np.zeros((8,), np.int32)
    padded[:prompt.size] = prompt

    @jax.jit
    def reference(margs, cache):
        # the first admission takes the last free slot
        _, logits = dec.prefill(margs, cache, jnp.int32(srv.slots - 1),
                                padded, jnp.int32(prompt.size))
        first, _ = sample_step(
            logits[None], jnp.asarray(key)[None],
            jnp.full((1,), method_id("sample"), jnp.int32),
            jnp.full((1,), 0.8, jnp.float32),
            jnp.full((1,), top_k, jnp.int32))
        return first[0]

    want = reference(tuple(dec.model_args()), dec.init_cache(srv.slots, 32))
    assert got == [int(want)]


# -- the store's disk entries -------------------------------------------------
def test_same_key_new_signature_compiles_instead_of_loading_stale(tmp_path):
    d = str(tmp_path / "exec")
    key = ("admit", 32, 8)
    old_args = (jax.ShapeDtypeStruct((4,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    new_args = (jax.ShapeDtypeStruct((12,), jnp.int32),)

    old = executables.FunctionStore("fp-sig", directory=d)
    old.register("admit", lambda a, b: a + b)
    old.load_or_compile(key, old_args)
    assert old.stats["compiles"] == 1

    def upgraded():
        s = executables.FunctionStore("fp-sig", directory=d)
        return s.register("admit", lambda p: p[:4] * p[4])

    new = upgraded()
    e = new.load_or_compile(key, new_args)
    assert new.stats["compiles"] == 1 and new.stats["disk_hits"] == 0
    assert new.stats["deserialize_failures"] == 0
    p = jnp.arange(12, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(e.call(p)),
                                  np.arange(4) * 4)
    # the new signature's own entry loads on the next restart
    warm = upgraded()
    e = warm.load_or_compile(key, new_args)
    assert warm.stats["disk_hits"] == 1 and warm.stats["compiles"] == 0
    np.testing.assert_array_equal(np.asarray(e.call(p)), np.arange(4) * 4)
