"""The five served families behind one generic decoder
(`generation/decode.py`'s `FamilyDecoder`), each from the slot state its
model file declares (`decode_leaves`): the programs the server compiles
over each, its fingerprint and its cache are what each family's own
decoder class gave before the classes became declarations."""
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

import test_afmoe
import test_deepseek_v3
import test_keye_vl
import test_lfm2_moe
import test_nemotron_h
from deeplearning4j_tpu.generation import decode
from deeplearning4j_tpu.generation.server import GenerationServer
from deeplearning4j_tpu.models import (afmoe, decoder_common, deepseek_v3,
                                       keye_vl, lfm2_moe, nemotron_h)

FAMILIES = {
    "nemotron_h": (nemotron_h, nemotron_h.NemotronHConfig, test_nemotron_h,
                   decode.NemotronHDecoder),
    "keye_vl": (keye_vl, keye_vl.KeyeVLConfig, test_keye_vl,
                decode.KeyeDecoder),
    "deepseek_v3": (deepseek_v3, deepseek_v3.DeepseekV3Config,
                    test_deepseek_v3, decode.MLADecoder),
    "afmoe": (afmoe, afmoe.AfmoeConfig, test_afmoe, decode.AfmoeDecoder),
    "lfm2_moe": (lfm2_moe, lfm2_moe.Lfm2MoeConfig, test_lfm2_moe,
                 decode.Lfm2MoeDecoder),
}

#: a family -> (sha256 of the superstep, the admit and the grow program,
#: `fingerprint()`, sha256 of the cache trees), as each family's own
#: decoder class gave them: the commit before the classes became one
AS_BEFORE = {
    "nemotron_h": (["e239a610d7f257e9", "134eb8d6dea844fb",
                    "4a44a2d47502a67b"], "eb9d4de4a6651ea4",
                   "97c8801524d30138"),
    "keye_vl": (["fcbce4f99e88a853", "cdb6894e7654ded5",
                 "126c43f5e584a1da"], "6b5b061c96a46df6",
                "18768194558b1321"),
    "deepseek_v3": (["1e3e35291d33a1bc", "170bf930c159a79f",
                     "27303c0db1db4952"], "4adefcb8907e87d4",
                    "1fc8c6c48a6e93dc"),
    "afmoe": (["e2d33e062522609c", "e15ae4c9842d10ea",
               "b70fbe50fccc5689"], "b5be68334b3f05f3",
              "f74604372ce442b1"),
    "lfm2_moe": (["8546d80d8bbad80c", "f8bca0bbd1d815fe",
                  "c29243258463176f"], "2341d23c9162cc97",
                 "8b54d83d6a997b2b"),
}


def _normalised(text):
    """StableHLO text with debug info, every op's location cut to its
    named-scope path (`layer0/attn/kv_write/dynamic_update_slice`): source
    files, lines and call stacks go, the scopes a profiler trace is read by
    stay."""
    defs, body = {}, []
    for line in text.splitlines():
        m = re.match(r"#(loc\d*) = loc\((.*)\)$", line)
        if m:
            defs[m[1]] = m[2]
        else:
            body.append(line)

    def name(ref):
        m = re.match(r'("(?:[^"\\]|\\.)*")(\(#loc\d*\))?$', defs[ref])
        return m[1] if m else '""'

    text = re.sub(r"loc\(#(loc\d*)\)", lambda m: f"loc({name(m[1])})",
                  "\n".join(body))
    text = re.sub(r'loc\(("(?:[^"\\]|\\.)*")\(#loc\d*\)\)', r"loc(\1)",
                  text)
    return re.sub(r"loc\(callsite\(#loc\d* at #loc\d*\)\)", 'loc("")', text)


def _tree(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return repr((str(treedef), [(l.shape, str(l.dtype)) for l in leaves]))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_programs_fingerprint_and_cache_are_as_before(family):
    """At toy size, through `GenerationServer` (two slots, a rung of 64, a
    prompt bucket of 32): the superstep and the admit, and `grow` from a
    rung of 8 to 64, lower to the StableHLO they lowered to before, named
    scopes included; `fingerprint()` is the one a restarted replica's
    stored programs are filed under; and the cache's leaves at rungs 8 and
    64, and grown from 8 to 64, have the shapes and dtypes they had."""
    module, config, toys, decoder = FAMILIES[family]
    cfg = config.from_dict(toys.TOY)
    params = jax.eval_shape(lambda k: module.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dec = decoder(cfg, params)
    srv = GenerationServer(dec, slots=2, cache_lengths=[64],
                           prompt_buckets=[32], max_new_tokens=8, seed=0)
    sds = jax.ShapeDtypeStruct
    try:
        state = srv._state_spec(64)
        with jax.default_matmul_precision("default"):
            step = jax.jit(srv._traced_superstep(srv.superstep)).lower(
                params, *state, sds((2, srv._step_width(64)), jnp.int32))
            admit = jax.jit(srv._traced_admit).lower(
                params, *state, sds((srv._admit_width(32),), jnp.int32))
            grow = jax.jit(lambda cache: dec.grow(cache, 64),
                           donate_argnums=(0,)).lower(srv._state_spec(8)[0])
    finally:
        srv.shutdown()
    texts = [_normalised(lowered.as_text(debug_info=True))
             for lowered in (step, admit, grow)]
    assert "layer0/" in texts[0] and "layer0/" in texts[1]
    programs = [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts]
    trees = [_tree(jax.eval_shape(lambda: dec.init_cache(2, r)))
             for r in (8, 64)] + [_tree(jax.eval_shape(
                 lambda: dec.grow(dec.init_cache(2, 8), 64)))]
    got = (programs, dec.fingerprint(),
           hashlib.sha256(repr(trees).encode()).hexdigest()[:16])
    assert got == AS_BEFORE[family], (got, trees)


def test_a_layer_takes_the_leaf_after_the_earlier_owners_of_its_name():
    """The one rule behind every family's cache: a layer's leaf of a name is
    the cache's leaf at the number of earlier layers that own one of that
    name (the hybrid's `*EMEM`: one K/V pair, two Mamba states and tails);
    a leaf's rows at a rung are the rung's positions packed `per_row` to a
    row, capped at a ring's rows, or none."""
    cfg = nemotron_h.NemotronHConfig.from_dict(test_nemotron_h.TOY)
    params = jax.eval_shape(lambda k: nemotron_h.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dec = decode.NemotronHDecoder(cfg, params)
    assert [[leaf.name for leaf in own]
            for own in nemotron_h.decode_leaves(cfg)] \
        == [["k", "v"], [], ["ssm", "conv"], [], ["ssm", "conv"]]
    cache = jax.eval_shape(lambda: dec.init_cache(2, 8))
    assert {name: len(leaves) for name, leaves in cache.items()
            if name != "counts"} == {"k": 1, "v": 1, "ssm": 2, "conv": 2}
    grown = dec.grow(dec.init_cache(2, 8), 24)
    assert [leaf.shape[1] for leaf in grown["k"] + grown["ssm"]] \
        == [24, cfg.mamba_num_heads, cfg.mamba_num_heads]
    Leaf = decoder_common.Leaf
    ring = Leaf("k", (32,), jnp.float32, cap=12)
    packed = Leaf("ki", (16,), jnp.float32, per_row=2)
    tail = Leaf("conv", (2, 64), jnp.float32, per_row=0)
    assert [ring.shape(3, r) for r in (8, 64)] == [(3, 8, 32), (3, 12, 32)]
    assert packed.shape(3, 64) == (3, 32, 16)
    assert tail.shape(3, 8) == tail.shape(3, 64) == (3, 2, 64)
    with pytest.raises(ValueError, match="must be even"):
        packed.shape(3, 9)
