"""The expert layer on a chip, against the same layer of another tree: wall
clock a call at the shapes the serving cells run, and the two results held
to each other. Skipped off the TPU. On a machine with one:

    env DL4J_TEST_ON_CHIP=1 DL4J_PARENT_TREE=<checkout> \\
        python -m pytest -s tests/test_grouped_matmul_on_chip.py

`DL4J_PARENT_TREE` is a checkout of the commit to compare with (for
instance `git archive <commit>` unpacked into a git-ignored directory of
this one); its `parallel/moe.py`, `kernels/grouped_matmul.py` and
`models/decoder_common.py` are loaded beside this tree's. Where a model has
a shared expert beside its routed ones (kanana-2, Trinity:
`decoder_common.moe`), the layer timed is that function, router and shared
expert included; elsewhere (Keye's gated experts, the hybrid's latent
ones) it is `routed_experts` alone. Each shape prints one JSON line:
milliseconds a call (median of `CALLS`, the two trees' calls alternating),
the pairs held.

`test_an_admits_expert_layer_in_runs_of_each_length_on_the_chip` needs no
other tree: LFM2's expert layer over a prompt of 16384 tokens in runs of
three lengths, timed and held to each other to the bit. Nor does
`test_prefill_lengths_kernel_alone_on_the_chip` (`-k prefill_lengths`): the
prefill attention kernel at 16384 rows over the pairs up to four prompt
lengths, beside the bucket's static grid.
"""
import importlib.util
import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import grouped_matmul
from deeplearning4j_tpu.models import decoder_common
from deeplearning4j_tpu.models.nemotron_h import relu2
from deeplearning4j_tpu.parallel import moe

CALLS = 30

#: id -> tokens, experts, top_k, held, width, expert width, gated, scale,
#: shared width (0: no shared expert in this layer): the long-context
#: cells' prefill runs of 2048 tokens (16 of 128 experts held) and
#: kanana-2's decode step of 48 slots, the hybrid's decode step and
#: long-bucket admit (128 of 512), and its one prompt of 4096 tokens, past
#: what one call holds (two runs)
SHAPES = {
    "keye_prefill_run": (2048, 128, 8, 16, 2048, 768, True, 1.0, 0),
    "trinity_prefill_run": (2048, 128, 8, 16, 2048, 1024, True, 2.826, 1024),
    "kanana2_prefill_run": (2048, 128, 6, 16, 2048, 768, True, 2.448, 1536),
    "kanana2_decode_step": (48, 128, 6, 16, 2048, 768, True, 2.448, 1536),
    "hybrid_decode_step": (128, 512, 22, 128, 1024, 2688, False, 5.0, 0),
    "hybrid_long_admit": (512, 512, 22, 128, 1024, 2688, False, 5.0, 0),
    "hybrid_prompt_4096": (4096, 512, 22, 128, 1024, 2688, False, 5.0, 0),
}


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def other_tree():
    """The other tree's (routed_experts, decoder_common.moe)."""
    if jax.default_backend() != "tpu":
        pytest.skip("times the expert layer on a TPU only")
    tree = os.environ.get("DL4J_PARENT_TREE")
    if not tree:
        pytest.skip("DL4J_PARENT_TREE names no tree to compare with")
    pkg = os.path.join(tree, "deeplearning4j_tpu")
    other = _load("other_moe", os.path.join(pkg, "parallel", "moe.py"))
    other.grouped_matmul = _load(
        "other_grouped_matmul",
        os.path.join(pkg, "kernels", "grouped_matmul.py"))
    common = _load("other_decoder_common",
                   os.path.join(pkg, "models", "decoder_common.py"))
    common.routed_experts = other.routed_experts
    return other.routed_experts, common.moe


def _operands(t, e, held, d, f, shared, seed):
    keys = iter(jax.random.split(jax.random.key(seed), 9))

    def mat(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.bfloat16)
    x = jax.random.normal(next(keys), (t, d), jnp.bfloat16)
    layer = dict(router=mat(d, e),
                 router_bias=0.01 * jax.random.normal(next(keys), (e,)),
                 w_gate=mat(held, d, f), w_up=mat(held, d, f),
                 w_down=mat(held, f, d))
    if shared:
        layer.update(s_gate=mat(d, shared), s_up=mat(d, shared),
                     s_down=mat(shared, d))
    return x, layer


def _ms_a_call(fns, args):
    """Median wall clock of each of `fns` over CALLS calls, interleaved."""
    times = [[] for _ in fns]
    for _ in range(CALLS):
        for fn, ts in zip(fns, times):
            start = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - start)
    return [1e3 * float(np.median(ts)) for ts in times]


@pytest.mark.parametrize("shape", list(SHAPES))
def test_expert_layer_time_against_another_tree_on_the_chip(other_tree,
                                                            shape):
    t, e, k, held, d, f, gated, scale, shared = SHAPES[shape]
    x, layer = _operands(t, e, held, d, f, shared, seed=t + k)
    cfg = types.SimpleNamespace(experts_held=(0, held),
                                num_experts_per_tok=k,
                                routed_scaling_factor=scale)
    other_routed, other_moe = other_tree

    def jitted(routed, common_moe):
        if shared:
            return jax.jit(lambda x, layer: common_moe(cfg, layer, x))

        def routed_layer(x, layer):
            scores = jax.nn.sigmoid(x.astype(jnp.float32)
                                    @ layer["router"].astype(jnp.float32))
            return routed(
                x, scores, layer["router_bias"], layer["w_up"],
                layer["w_down"], (0, held), k, scale,
                jax.nn.silu if gated else relu2, impl="pallas",
                w_gate=layer["w_gate"] if gated else None)
        return jax.jit(routed_layer)

    this = jitted(moe.routed_experts, decoder_common.moe)
    other = jitted(other_routed, other_moe)
    args = (x, layer)
    # the numerics the programs run with, not the CPU oracles' "highest"
    with jax.default_matmul_precision("default"):
        (got, got_counts), (want, want_counts) = this(*args), other(*args)
        this_ms, other_ms = _ms_a_call([this, other], args)
    print(json.dumps({"shape": shape, "this_ms": round(this_ms, 4),
                      "other_ms": round(other_ms, 4),
                      "pairs_held": int(got_counts[0]), "pairs": t * k}))
    assert list(np.asarray(got_counts)) == list(np.asarray(want_counts))
    # the same products; only the order of each row's float32 sum differs
    np.testing.assert_allclose(
        got, want, rtol=0, atol=1e-5 * float(jnp.abs(want).max()))


#: LFM2's expert layer in an admit of its largest bucket: tokens, experts
#: (all held), top_k, width, expert width (gated)
LFM2_ADMIT = (16384, 64, 4, 2048, 1536)

#: run length -> how the layer is cut: the runs of `MOE_CHUNK` it took
#: under `lax.map` before its kernel took whole prompts (router and experts
#: a run, each run reading every expert it uses), the kernel's own runs
#: where a call's rows may take 40 MiB whatever its weight blocks leave (6
#: of 2736), and the runs it takes now (4 of 4096)
LFM2_RUNS = {2048: "lax_map", 2736: 40 << 20, 4096: None}


def test_an_admits_expert_layer_in_runs_of_each_length_on_the_chip(
        monkeypatch):
    """LFM2's expert layer (`decoder_common.moe`, no shared expert) in an
    admit of 16384 tokens: 64 experts of 2048 x 1536 gated, 4 a token, all
    held. Each run length prints one JSON line: milliseconds a call
    (median of `CALLS`, the lengths' calls alternating), the calls, the
    row tiles they visit and the experts they read. A token's router row,
    its pairs and their sum do not depend on the tokens beside it, so the
    layer's output added to its input is the same bits whatever the
    length, and every pair is
    counted (`routed_experts` counts the experts read over its call, the
    runs under `lax.map` theirs each)."""
    import types
    if jax.default_backend() != "tpu":
        pytest.skip("times the expert layer on a TPU only")
    t, e, k, d, f = LFM2_ADMIT
    x, layer = _operands(t, e, e, d, f, 0, seed=t + k)
    cfg = types.SimpleNamespace(experts_held=(0, e), num_experts_per_tok=k,
                                routed_scaling_factor=1.0,
                                route_norm_eps=1e-6)

    # each as the model adds the layer to its input (`lfm2_moe.
    # feed_forward`): the stacked float32 results of `lax.map` returned as
    # they are make XLA fuse their update into the kernel's output, which
    # then needs more than the 16 MiB of VMEM such a fusion is given
    def whole(x):
        out, counts = decoder_common.moe(cfg, layer, x)
        return x + out.astype(x.dtype), counts

    def in_runs(run):
        def layer_in_runs(x):
            out, counts = jax.lax.map(
                lambda a: decoder_common.moe(cfg, layer, a),
                x.reshape(t // run, run, d))
            return x + out.reshape(t, d).astype(x.dtype), jnp.concatenate(
                [counts[:, :2].sum(0), counts[:, 2:].max(0)])
        return layer_in_runs

    fns, results, calls = {}, {}, {}
    tm = moe._row_tile(t * k / e)
    # the numerics the programs run with, not the CPU oracles' "highest"
    with jax.default_matmul_precision("default"):
        for run, how in LFM2_RUNS.items():
            fns[run] = jax.jit(in_runs(run) if how == "lax_map" else whole)
            with monkeypatch.context() as m:
                if how != "lax_map" and how is not None:
                    m.setattr(grouped_matmul, "_CALL_VMEM",
                              how + grouped_matmul.weight_bytes(
                                  2 * d, f, d, 2))
                calls[run] = (t // run, run) if how == "lax_map" else \
                    moe.kernel_runs(t, k, tm, jnp.bfloat16, layer["w_up"],
                                    layer["w_down"], layer["w_gate"])
                results[run] = fns[run](x)                # traced here
            assert calls[run][1] == run, calls[run]
        times = _ms_a_call(list(fns.values()), (x,))
        scores = decoder_common.router_scores(layer, x)
    want, want_counts = results[max(LFM2_RUNS)]
    _, idx = jax.lax.top_k(scores + layer["router_bias"], k)
    idx = np.asarray(idx)
    for run, ms in zip(fns, times):
        got, counts = results[run]
        sizes = [np.bincount(idx[i:i + run].reshape(-1), minlength=e)
                 for i in range(0, t, run)]
        print(json.dumps({
            "shape": "lfm2_admit_16384", "run": run, "calls": calls[run][0],
            "ms": round(ms, 4),
            "tiles": int(sum((-(-s // tm)).sum() for s in sizes)),
            "expert_reads": int(sum((s > 0).sum() for s in sizes))}))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=str(run))
        assert int(counts[0]) == int(want_counts[0]) == t * k


#: the prefill attention kernel alone at the long-context cells' largest
#: bucket: id -> heads, keys' width, values' width (32 heads, 16384 rows,
#: tiles of 1024, bfloat16 products); and the prompt lengths timed
PREFILL_SHAPES = {"kanana2": (32, 192, 128), "trinity_full": (32, 128, 128)}
PREFILL_LENGTHS = (16384, 15000, 11819, 9000)


@pytest.mark.parametrize("shape", list(PREFILL_SHAPES))
def test_prefill_lengths_kernel_alone_on_the_chip(shape):
    """`flash_attention(..., causal=True, lengths=)` over a bucket of 16384
    at each of `PREFILL_LENGTHS`, beside the call without `lengths` (the
    bucket's static grid). Each prints one JSON line: milliseconds a call
    (median of `CALLS`, the calls alternating), the grid steps one head
    runs and the static grid's. The rows of the q tiles in use are the
    length-less call's bits; the rest are zeros."""
    fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")
    if jax.default_backend() != "tpu":
        pytest.skip("times the prefill kernel on a TPU only")
    h, d, dv = PREFILL_SHAPES[shape]
    t, block = 16384, 1024
    keys = jax.random.split(jax.random.PRNGKey(t + d), 3)
    q, k = (jax.random.normal(kk, (1, h, t, d), jnp.bfloat16)
            for kk in keys[:2])
    v = jax.random.normal(keys[2], (1, h, t, dv), jnp.bfloat16)

    def call(lengths=None):
        return jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            native=True, lengths=lengths))

    fns = [call()] + [call(jnp.asarray([n], jnp.int32))
                      for n in PREFILL_LENGTHS]
    with jax.default_matmul_precision("default"):
        outs = [np.asarray(fn(q, k, v).astype(jnp.float32)) for fn in fns]
        times = _ms_a_call(fns, (q, k, v))
    for n, out, ms in zip((None,) + PREFILL_LENGTHS, outs, times):
        run, static = fa.prefill_grid_steps(t if n is None else n, t, block,
                                            block)
        print(json.dumps({"shape": f"{shape}_prefill_16384", "length": n,
                          "ms": round(ms, 4),
                          "steps_a_head": static if n is None else run,
                          "static_steps_a_head": static}))
        if n is not None:
            end = -(-n // block) * block
            np.testing.assert_array_equal(out[:, :, :end],
                                          outs[0][:, :, :end], err_msg=n)
            assert not out[:, :, end:].any()
