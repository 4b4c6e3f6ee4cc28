"""CPU rehearsal of the sliding-window cell at toy size, run by hand (not
part of tier-1), its controls and kernels on the chip, and its byte and
operation counts against values reckoned by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

The family, driver `serve_closed_window` and reader `window_share` end to
end through `run.run_cell`. Nothing here is a measurement: a time from a
CPU run is never a device number."""
from __future__ import annotations

import copy
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import work_window  # noqa: E402
from benchmarks.readers import window_share  # noqa: E402
from benchmarks.families.afmoe_serve import FAULTS  # noqa: E402
from benchmarks.tests.test_rehearsal_sparse import _StandIn  # noqa: E402

CELL = "trinity_mini_serve_longdoc"
CONFIG = "trinity_mini_ep8"


@pytest.fixture
def toy():
    """The published model's shapes at toy widths (as tests/test_afmoe.py):
    the published layer pattern's first period, S S S F, over a window of
    16 rows, both dense layers and two expert layers, the second of two
    shares of 8 experts, half the vocabulary; prompts of 20-60, so every
    ring wraps."""
    config = copy.deepcopy(run.load("configs", CONFIG))
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=4, num_hidden_layers=4,
        sliding_window=16, vocab_size=96)
    config["published"].update(num_experts=16, vocab_size=192)
    config["held"].update(experts=[8, 16])
    config["serving"].update(dtype="float32", slots=4, cache_lengths=[96],
                             prompt_buckets=[32, 64])
    workload = copy.deepcopy(run.load("workloads", CELL))
    workload.update(clients=4, warmup_seconds=0.5)
    workload["requests"].update(
        distinct=16, prompt_len={"dist": "log_uniform", "lo": 20, "hi": 60},
        output_len={"dist": "log_uniform", "lo": 4, "hi": 8})
    return workload, config


def _build(config, seed):
    return importlib.import_module(
        "benchmarks.families.afmoe_serve").build(config, seed)


def _chooser(built, pick, **how):
    """`_StandIn`'s choice of token from the plain reference's logits,
    computed as `how` says (`lower=True`, `fault=` one of `FAULTS`)."""
    return lambda ids, at: pick(built.reference_logits(
        ids, at=at[:, None], **how)[:, 0])


def _driver(built, workload, seed, cache_dir, on_chip):
    driver = importlib.import_module(
        "benchmarks.drivers.serve_closed_window").Driver(
            built, workload, seed, cache_dir, on_chip)
    driver.setup()
    driver.warm()
    return driver


def test_window_cell_end_to_end(toy, tmp_path, capsys):
    import jax
    workload, config = toy
    devices = jax.devices()[:1]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": 1}
    result = run.run_cell(CELL, workload, config, 2**31 + 41, 3.0, False,
                          devices, device, cache_dir=str(tmp_path),
                          on_chip=False)
    assert result["correct"] is True, capsys.readouterr().out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                      "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    out = capsys.readouterr().out
    assert "gaps to the reference's largest logit" in out
    assert "8 probes at once" in out
    assert "window and full attention and expert layers" in out


def test_driver_takes_the_counters_over_the_window(toy, tmp_path):
    workload, config = toy
    driver = _driver(_build(config, 5), workload, 5, str(tmp_path), False)
    assert driver.check()
    driver.measure(2.0)
    steps = driver.context["status_delta"]["steps"]
    delta = driver.context["window_delta"]
    assert steps > 0
    # 3 sliding layers x 4 slots of at most 16 ring rows; 1 full layer x 4
    # slots of at most the 96-row rung
    assert 0 < delta["window_rows_attended"] <= 3 * 4 * 16 * steps
    assert 0 < delta["global_rows_attended"] <= 1 * 4 * 96 * steps
    # 2 expert layers of 8 held experts
    assert 0 < delta["moe_expert_reads"] <= 2 * 8 * steps
    ctx = {"driver": driver.context, "config": config, "trace": None}
    # no trace (and, on the parent, no counters): nothing to read
    for spec in ({"key": "step_hbm_share"}, {"key": "prefill_mfu"},
                 {"key": "rows_hbm_share",
                  "counter": "window_rows_attended"},
                 {"key": "scopes_ms", "scopes": ["window"],
                  "program": "superstep"}):
        assert window_share.read(ctx, spec) is None
    ctx["driver"] = {"status_delta": {"steps": 3, "tokens": 9}}
    ctx["trace"] = {"span": (0, 1)}
    for spec in ({"key": "step_hbm_share"}, {"key": "prefill_mfu",
                                              "scopes": ["window"],
                                              "program": "admit"},
                 {"key": "rows_hbm_share", "counter": "global_rows_attended",
                  "scopes": ["global"], "program": "superstep"}):
        assert window_share.read(ctx, spec) is None


def test_check_refuses_streams_the_reference_ranks_last(toy, tmp_path):
    """`check()` itself says no: the served streams pass, the reference's
    own greedy streams pass, its least likely token at every position does
    not."""
    workload, config = toy
    built = _build(config, 7)
    driver = _driver(built, workload, 7, str(tmp_path), False)
    srv = driver.srv
    try:
        assert driver.check() is True
        driver.srv = _StandIn(srv, _chooser(built, lambda l: l.argmax(-1)))
        assert driver.check() is True
        driver.srv = _StandIn(srv, _chooser(built, lambda l: l.argmin(-1)))
        assert driver.check() is False
        assert "8 probes at once" in driver.notes[-1]
    finally:
        srv.shutdown()


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_reach_the_stand_in(toy, fault):
    """Each planted fault is another function than the reference (at toy
    widths, through the family's jitted entry that the controls use); what
    `check()` makes of it is decided at the published widths, on the
    chip."""
    import numpy as np
    _, config = toy
    built = _build(config, 9)
    ids = np.arange(1, 41, dtype=np.int32)[None] % built.vocab
    at = np.asarray([[39]], np.int32)
    plain = built.reference_logits(ids, at=at)
    assert np.array_equal(plain, built.reference_logits(ids, at=at,
                                                        fault=None))
    assert np.abs(built.reference_logits(ids, at=at, fault=fault)
                  - plain).max() > 1e-4


def _on_chip_or_skip(what):
    import jax
    if jax.default_backend() != "tpu":
        pytest.skip(f"{what} at the published widths, on a TPU")
    from deeplearning4j_tpu.runtime import executables
    executables.configure_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def test_controls_fail_check_on_the_chip():
    """The controls of `LOGIT_TOLERANCE`, at the published widths, on the
    chip only (`python3 -m pytest -s -k controls_fail
    benchmarks/tests/test_rehearsal_window.py` on a TPU): the served
    streams are correct; the streams the reference picks one precision
    below the configuration's (float8 weights, block inputs, cached rows,
    the residual stream) are NOT, by the same `check()`; and neither are the
    streams of a reference with a fault planted in its attention
    (`FAULTS`: the window ignored, rotary on the full layers, the gate
    dropped), which is what says that `correct` sees the rings, the full
    leaves and the gate and not the embedding and the head alone."""
    _on_chip_or_skip("the controls run")
    seed = int(os.environ.get("WINDOW_CONTROL_SEED", 3_000_004_101))
    workload = run.load("workloads", CELL)
    config = run.load("configs", workload["config"])
    built = _build(config, seed)
    driver = _driver(built, workload, seed, run.CACHE, True)
    srv = driver.srv
    try:
        verdicts = {"served": driver.check()}
        for name, how in [("float8", {"lower": True})] + [
                (fault, {"fault": fault}) for fault in FAULTS]:
            driver.srv = _StandIn(srv, _chooser(
                built, lambda l: l.argmax(-1), **how))
            verdicts[name] = driver.check()
        print("\n".join(driver.notes))
        print(f"seed {seed}: correct by check(): {verdicts}")
        assert verdicts.pop("served") is True
        assert not any(verdicts.values()), verdicts
    finally:
        srv.shutdown()


def test_served_gaps_on_the_chip():
    """The served path's largest gap to the reference over more sets of
    weights than one run reads, at the published widths, on the chip only
    (`-k served_gaps`; `WINDOW_GAP_SEEDS`, comma-separated, the seeds):
    `check()` over each seed's eight probes, its note printed, so that
    `LOGIT_TOLERANCE` is set from many probe tokens."""
    _on_chip_or_skip("the served gaps are read")
    import gc
    seeds = [int(s) for s in os.environ.get(
        "WINDOW_GAP_SEEDS", "3000004102,3000004103").split(",")]
    workload = run.load("workloads", CELL)
    config = run.load("configs", workload["config"])
    for seed in seeds:
        driver = _driver(_build(config, seed), workload, seed, run.CACHE,
                         True)
        try:
            verdict = driver.check()
            print(f"seed {seed}: correct {verdict}; {driver.notes[-1]}")
        finally:
            driver.srv.shutdown()
        del driver
        gc.collect()


def test_ring_decode_tiles_on_the_chip():
    """The decode kernel over a full ring `(48, 2048, 512)` (every row in
    use once a prompt passes the window) and over the full layers' leaf
    `(48, 18432, 512)` under the cell's lengths, in tiles of 512, 1024 and
    2048 rows (`-k ring_decode_tiles`): wall clock a call, the median of
    12."""
    _on_chip_or_skip("the decode tiles are measured")
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.kernels.flash_attention import \
        flash_attention_decode

    config = run.load("configs", CONFIG)
    slots, rung, window = int(config["serving"]["slots"]), \
        int(config["serving"]["cache_lengths"][0]), config["sliding_window"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    rng = np.random.default_rng(41)
    in_use = rng.integers(4096, rung, slots).astype(np.int32)
    q1 = jax.random.normal(jax.random.key(1), (slots, 32, 128),
                           jnp.bfloat16)
    for name, rows, lengths in (("ring", window, np.full(slots, window)),
                                ("full", rung, in_use)):
        kc, vc = (jax.random.normal(kk, (slots, rows, kv), jnp.bfloat16)
                  for kk in jax.random.split(jax.random.key(rows), 2))
        n = jnp.asarray(lengths, jnp.int32)
        mask = jnp.arange(rows)[None, :] < n[:, None]
        want = None
        for tile in (512, 1024, 2048):
            fn = jax.jit(lambda q, k, v, m, n, t=tile: flash_attention_decode(
                q, k, v, m, impl="pallas", lengths=n, block_k=t))
            out = jax.block_until_ready(fn(q1, kc, vc, mask, n))
            ts = []
            for _ in range(12):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(q1, kc, vc, mask, n))
                ts.append(time.perf_counter() - t0)
            ms = 1e3 * float(np.median(ts))
            gap = 0.0 if want is None else float(jnp.abs(
                out.astype(jnp.float32) - want).max())
            want = out.astype(jnp.float32) if want is None else want
            print(f"decode over the {name} ({slots}, {rows}, {kv}), tiles "
                  f"of {tile}: {ms:.4f} ms a call, "
                  f"{int(lengths.sum()) * 2 * kv * 2 / ms / 1e6:.1f} GB/s "
                  f"over the rows in use; gap to tiles of 512 {gap:.5f}")
        del kc, vc


def test_seeded_draw_on_the_chip():
    """What the seeded weights give at the published widths, on the chip
    only (`-k seeded_draw`): the reference's argmax over the last 512
    positions of a 4096-token prompt, how many are distinct, and how far
    each planted fault and the lower precision move those positions'
    logits (rms and largest): a draw under which `correct` compares
    nothing shows here as few distinct tokens or faults that move
    nothing."""
    _on_chip_or_skip("the seeded draw is read")
    import numpy as np
    config = run.load("configs", CONFIG)
    built = _build(config, 3_000_004_101)
    rng = np.random.default_rng(41)
    ids = rng.integers(1, built.vocab, (1, 4096)).astype(np.int32)
    at = np.arange(3584, 4096, dtype=np.int32)[None]
    plain = built.reference_logits(ids, at=at)[0]
    top = plain.argmax(-1)
    print(f"seeded draw: {len(set(top.tolist()))} distinct argmax of "
          f"{len(top)} late positions, max |logit| "
          f"{float(np.abs(plain).max()):.3f}, median gap between the two "
          f"best {float(np.median(np.diff(np.sort(plain, -1)[:, -2:]))):.4f}")
    for name, how in [("float8", {"lower": True})] + [
            (fault, {"fault": fault}) for fault in FAULTS]:
        other = built.reference_logits(ids, at=at, **how)[0]
        moved = other - plain
        rms = float(np.sqrt((moved ** 2).mean()))
        print(f"  {name}: logits moved by {rms:.4f} "
              f"rms, {float(np.abs(moved).max()):.4f} at most; argmax "
              f"changed at {int((other.argmax(-1) != top).sum())} of "
              f"{len(top)}")
    assert len(set(top.tolist())) > len(top) // 4


def test_kernels_alone_on_the_chip():
    """The banded prefill kernel alone at the cell's shapes, on the chip
    only (`python3 -m pytest -s -k kernels_alone
    benchmarks/tests/test_rehearsal_window.py` on a TPU): 32 query heads
    of 128 (the 4 KV heads repeated), bfloat16, prompts of 8192 and
    16384, window 2048, in tiles of 512 and 1024, against the CAUSAL call
    on the same operands; the committed tile against a dense banded
    softmax; and the decode kernel over the ring `(48, 2048, 512)` and a
    full leaf `(48, 18432, 512)` under lengths drawn as the cell's. Wall
    clock, the median of 6 repeats, printed."""
    _on_chip_or_skip("the kernels are measured")
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.kernels.flash_attention import (
        decode_tile_rows, flash_attention, flash_attention_decode)
    from deeplearning4j_tpu.models.afmoe import PREFILL_BLOCK

    def timed(fn, *args, reps):
        jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t)
        return float(np.median(ts))

    config = run.load("configs", CONFIG)
    heads, d, window = (config["num_attention_heads"], config["head_dim"],
                        config["sliding_window"])
    out = {"committed_block": PREFILL_BLOCK}
    for t in config["serving"]["prompt_buckets"]:
        ks = jax.random.split(jax.random.key(t), 3)
        q, k, v = (jax.random.normal(kk, (1, heads, t, d), jnp.bfloat16)
                   for kk in ks)
        pairs = work_window.band_pairs(t, window)
        for block in (512, 1024):
            for name, w in (("band", window), ("causal", None)):
                fn = jax.jit(lambda q, k, v, b=block, w=w: flash_attention(
                    q, k, v, causal=True, block_q=b, block_k=b, native=True,
                    window=w))
                ms = 1e3 * timed(fn, q, k, v, reps=6)
                n = pairs if w else t * (t + 1) // 2
                out[f"{name}_{t}_{block}"] = {"ms": ms, "pairs": n}
                print(f"{name}, {t} tokens in tiles of {block}: {ms:.2f} ms "
                      f"({4 * heads * d * n / ms / 1e9:.1f} TFLOP/s over "
                      f"the pairs needed)")
    n = 4096                # a dense banded softmax of 16384 is 34 GB
    q, k, v = (a[:, :, :n] for a in (q, k, v))
    got = flash_attention(q, k, v, causal=True, block_q=PREFILL_BLOCK,
                          block_k=PREFILL_BLOCK, native=True, window=window)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                    preferred_element_type=jnp.float32) / d ** 0.5
    tq, tk = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    seen = (tk <= tq) & (tk > tq - window)
    dense = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(seen, sc, -jnp.inf), -1).astype(jnp.bfloat16), v,
        preferred_element_type=jnp.float32)
    gap = float(jnp.abs(got.astype(jnp.float32) - dense).max())
    limit = 2 * 2.0 ** -8 * float(jnp.abs(dense).max())
    out["band_gap"] = {"gap": gap, "limit": limit}
    print(f"band at {PREFILL_BLOCK}, {n} tokens: gap to the dense banded "
          f"softmax {gap:.5f} (limit {limit:.5f})")
    del q, k, v, sc, dense
    slots, rung = int(config["serving"]["slots"]), \
        int(config["serving"]["cache_lengths"][0])
    kv = config["num_key_value_heads"] * d
    rng = np.random.default_rng(41)
    spec = run.load("workloads", CELL)["requests"]
    prompt, output = (np.exp(rng.uniform(
        *np.log([spec[name]["lo"], spec[name]["hi"]]), slots))
        for name in ("prompt_len", "output_len"))
    in_use = np.minimum(prompt + rng.uniform(0, 1, slots) * output,
                        rung).astype(np.int32)
    q1 = jax.random.normal(jax.random.key(1), (slots, heads, d),
                           jnp.bfloat16)
    for name, rows, lengths in (
            ("ring", window, np.minimum(in_use, window)),
            ("full", rung, in_use)):
        kc, vc = (jax.random.normal(kk, (slots, rows, kv), jnp.bfloat16)
                  for kk in jax.random.split(jax.random.key(rows), 2))
        n_ = jnp.asarray(lengths)
        mask = jnp.arange(rows)[None, :] < n_[:, None]
        fn = jax.jit(lambda q, k, v, m, n: flash_attention_decode(
            q, k, v, m, impl="pallas", lengths=n))
        ms = 1e3 * timed(fn, q1, kc, vc, mask, n_, reps=12)
        need = int(lengths.sum()) * 2 * kv * 2
        out[f"decode_{name}"] = {"ms": ms, "rows": int(lengths.sum()),
                                 "tile": decode_tile_rows(rows, kv,
                                                          jnp.bfloat16)}
        print(f"decode over the {name} ({slots}, {rows}, {kv}): {ms:.4f} ms "
              f"a layer, tile {out[f'decode_{name}']['tile']}, "
              f"{need / ms / 1e6:.1f} GB/s over the rows in use")
        del kc, vc
    assert gap <= limit
    for t in config["serving"]["prompt_buckets"]:
        assert out[f"band_{t}_{PREFILL_BLOCK}"]["ms"] \
            < out[f"causal_{t}_{PREFILL_BLOCK}"]["ms"]


def test_counts_against_hand_reckoned_values():
    """The published widths (the configuration file as committed)."""
    c = run.load("configs", CONFIG)
    assert (work_window.layers_of(c), work_window.dense_layers_of(c),
            work_window.sliding_layers_of(c)) == (8, 2, 6)
    # a cached position: k and v of 4 heads of 128, 2 bytes
    assert work_window.kv_row_bytes(c) == 2048
    # W_q, W_gate, W_o 2048 x 4096 each; W_k, W_v 2048 x 512
    assert work_window.attention_params(c) \
        == 3 * 8_388_608 + 2 * 1_048_576 == 27_262_976
    assert work_window.expert_params(c) == 6_291_456       # 3 x 2048 x 1024
    assert work_window.shared_params(c) == 6_291_456
    assert work_window.router_params(c) == 262_144         # 2048 x 128
    assert work_window.dense_ffn_params(c) == 37_748_736   # 3 x 2048 x 6144
    assert work_window.head_params(c) == 51_249_152        # 2048 x 25024
    assert work_window.resident_params(c) \
        == 8 * 27_262_976 + 2 * 37_748_736 + 6 * (262_144 + 6_291_456) \
        + 51_249_152 == 384_172_032
    # 48 slots, 6 rings of 2048 rows in use and 2 full layers at 9600
    ring, full = 6 * 48 * 2048, 2 * 48 * 9600
    assert work_window.rows_bytes(c, ring) == 1_207_959_552
    assert work_window.decode_step_bytes(c, ring, full, 90) \
        == (ring + full) * 2048 + 2 * (384_172_032 + 90 * 6_291_456)
    assert work_window.moe_step_bytes(c, 90) \
        == 2 * (6 * (262_144 + 6_291_456) + 90 * 6_291_456)
    # the band of a prompt: t + 1 keys up to the window, then 2048 a query
    assert work_window.band_pairs(5, 2048) == 15
    assert work_window.band_pairs(16384, 2048) \
        == 2048 * 2049 // 2 + (16384 - 2048) * 2048 == 31_458_304
    assert work_window.prefill_window_flops(c, 16384) \
        == 31_458_304 * 4 * 4096 * 6
