"""CPU rehearsal of the latent-attention cell at toy size, run by hand (not
part of tier-1), and its byte and operation counts against values reckoned
by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

The family, driver `serve_closed_latent` and reader `latent_share` end to
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
from benchmarks.harness import work_latent  # noqa: E402
from benchmarks.readers import latent_share  # noqa: E402
from benchmarks.families.deepseek_v3_serve import FAULTS  # noqa: E402
from benchmarks.tests.test_rehearsal_sparse import _StandIn  # noqa: E402

CELL = "kanana2_serve_longdoc"
CONFIG = "kanana2_30b_a3b_ep8"


@pytest.fixture
def toy():
    """Every ratio of the published model at toy widths (as
    tests/test_deepseek_v3.py): a dense layer and two expert layers, the
    second of two shares of 8 experts, half the vocabulary."""
    config = copy.deepcopy(run.load("configs", CONFIG))
    config.update(
        hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=96, moe_intermediate_size=48, n_routed_experts=8,
        num_experts_per_tok=4, num_hidden_layers=3, vocab_size=96)
    config["published"].update(n_routed_experts=16, vocab_size=192)
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
        "benchmarks.families.deepseek_v3_serve").build(config, seed)


def _chooser(built, pick, **how):
    """`_StandIn`'s choice of token from the plain reference's logits,
    computed as `how` says (`lower=True`, `fault=` one of `FAULTS`)."""
    return lambda ids, at: pick(built.reference_logits(
        ids, at=at[:, None], **how)[:, 0])


def _driver(built, workload, seed, cache_dir, on_chip):
    driver = importlib.import_module(
        "benchmarks.drivers.serve_closed_latent").Driver(
            built, workload, seed, cache_dir, on_chip)
    driver.setup()
    driver.warm()
    return driver


def test_latent_cell_end_to_end(toy, tmp_path, capsys):
    import jax
    workload, config = toy
    devices = jax.devices()[:1]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": 1}
    result = run.run_cell(CELL, workload, config, 2**31 + 37, 3.0, False,
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
    assert "latent attention and expert layers over the window" in out


def test_driver_takes_the_counters_over_the_window(toy, tmp_path):
    workload, config = toy
    driver = _driver(_build(config, 5), workload, 5, str(tmp_path), False)
    assert driver.check()
    driver.measure(2.0)
    steps = driver.context["status_delta"]["steps"]
    delta = driver.context["latent_delta"]
    assert steps > 0
    # 3 layers x 4 slots, at most the 96-row rung each; the rung is one tile
    assert 0 < delta["mla_rows_attended"] <= 3 * 4 * 96 * steps
    assert delta["mla_rows_read"] == 3 * 4 * 96 * steps
    # 2 expert layers of 8 held experts
    assert 0 < delta["moe_expert_reads"] <= 2 * 8 * steps
    ctx = {"driver": driver.context, "config": config, "trace": None}
    # no trace (and, on the parent, no counters): nothing to read
    for key in ("step_hbm_share", "step_mfu", "rows_hbm_share", "rows_mfu",
                "moe_hbm_share"):
        assert latent_share.read(ctx, {"key": key}) is None
    # the two ratios of counters need no trace: the fullest of 8 held
    # experts has between the mean's pairs and all of them; the kernel
    # fetched the whole one-tile rung for the rows in use
    assert 1.0 <= latent_share.read(ctx, {"key": "load_max_over_mean"}) <= 8
    assert latent_share.read(ctx, {"key": "rows_read_over_attended"}) \
        == delta["mla_rows_read"] / delta["mla_rows_attended"] >= 1.0
    assert latent_share.read(ctx, {
        "key": "scopes_ms", "scopes": ["flash_decode"],
        "program": "superstep"}) is None
    ctx["driver"] = {"status_delta": {"steps": 3, "tokens": 9}}
    ctx["trace"] = {"span": (0, 1)}
    for key in ("step_mfu", "moe_hbm_share", "load_max_over_mean",
                "rows_read_over_attended"):
        assert latent_share.read(ctx, {"key": key}) is None


def test_check_refuses_streams_the_reference_ranks_last(toy, tmp_path):
    """`check()` itself says no: the served streams pass, the reference's
    own greedy streams pass, its least likely token at every position does
    not (at toy widths a lower precision moves logits by less than the
    limit; the chip's controls are the next test)."""
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


def test_controls_fail_check_on_the_chip():
    """The controls of `LOGIT_TOLERANCE`, at the published widths, on the
    chip only (`chiprun -- python3 -m pytest -s -k on_the_chip
    benchmarks/tests/test_rehearsal_latent.py`): the served streams are
    correct; the streams the reference picks one precision below the
    configuration's (float8 weights, block inputs and latent rows) are
    NOT, by the same `check()`; and neither are the streams of a reference
    with a fault planted in its attention (`FAULTS`: the attention left
    out, each latent row one position late beside its rotary key, the odd
    positions never attended), which is what says that `correct` sees the
    latent cache and the kernel that reads it and not the embedding and
    the head alone."""
    import jax
    if jax.default_backend() != "tpu":
        pytest.skip("the controls run at the published widths, on a TPU")
    from deeplearning4j_tpu.runtime import executables
    executables.configure_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    seed = int(os.environ.get("LATENT_CONTROL_SEED", 3_000_003_701))
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


def test_kernels_alone_on_the_chip():
    """The two attention kernels alone at the cell's shapes, on the chip
    only (`chiprun -- python3 -m pytest -s -k on_the_chip
    benchmarks/tests/test_rehearsal_latent.py`): the tables behind
    `latent_tile_positions` (kernels/mla_attention.py) and `PREFILL_BLOCK`
    (models/deepseek_v3.py), and each kernel against a dense masked softmax.
    `mla_decode` at (48, 9216, 1152) bfloat16 under positions drawn as the
    cell's, in tiles of 256-2048 positions; `flash_fwd` at 32 heads, keys
    of 192 and values of 128, 8192 and 16384 tokens. Wall clock, the median
    of 12 (6) repeats; the table goes to
    `chiprun_out/kernel_bench_latent.json` too."""
    import jax
    if jax.default_backend() != "tpu":
        pytest.skip("the kernels are measured at the cell's shapes, on a TPU")
    import json
    import time

    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.kernels.flash_attention import flash_attention
    from deeplearning4j_tpu.kernels.mla_attention import (
        latent_tile_positions, mla_attention_decode)
    from deeplearning4j_tpu.models.deepseek_v3 import PREFILL_BLOCK

    def timed(fn, *args, reps):
        jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t)
        return float(np.median(ts))

    config = run.load("configs", CONFIG)
    slots, rung = (int(config["serving"]["slots"]),
                   int(config["serving"]["cache_lengths"][0]))
    lat, rope, heads, layers = (
        config["kv_lora_rank"], config["qk_rope_head_dim"],
        config["num_attention_heads"], config["num_hidden_layers"])
    nope, vd = config["qk_nope_head_dim"], config["v_head_dim"]
    rng = np.random.default_rng(37)
    spec = run.load("workloads", CELL)["requests"]
    prompt, output = (np.exp(rng.uniform(
        *np.log([spec[name]["lo"], spec[name]["hi"]]), slots))
        for name in ("prompt_len", "output_len"))
    # each slot somewhere along its answer
    in_use = np.minimum(prompt + rng.uniform(0, 1, slots) * output,
                        rung).astype(np.int32)
    lengths = jnp.asarray(in_use)
    keys = jax.random.split(jax.random.key(0), layers + 2)
    leaves = [jax.random.normal(k, (slots, rung // 2, 2 * (lat + rope)),
                                jnp.bfloat16) for k in keys[:layers]]
    q_lat = jax.random.normal(keys[-2], (slots, heads, lat), jnp.bfloat16)
    q_rope = jax.random.normal(keys[-1], (slots, heads, rope), jnp.bfloat16)
    # normal rows: scores of a few units, not a one-hot softmax
    scale = (nope + rope) ** -0.5 / 8
    want = jax.jit(lambda *a: mla_attention_decode(*a, scale, impl="dense"))(
        q_lat, q_rope, leaves[0], lengths).astype(jnp.float32)
    # both sides round their result to bfloat16: two units in the last
    # place of the largest value (0.00049 read where the limit is 0.0045)
    limit = 2 * 2.0 ** -8 * float(jnp.abs(want).max())
    committed = latent_tile_positions(rung, lat, jnp.bfloat16)
    out = {"rows_in_use": int(in_use.sum()), "committed_tile": committed,
           "gap_limit": limit}
    for tile in sorted({256, 512, 1024, 2048, committed}):
        fn = jax.jit(lambda ql, qr, ls, n, tile=tile: [
            mla_attention_decode(ql, qr, leaf, n, scale, impl="pallas",
                                 block_k=tile) for leaf in ls])
        ms = 1e3 * timed(fn, q_lat, q_rope, leaves, lengths, reps=12) / layers
        gap = float(jnp.abs(fn(q_lat, q_rope, leaves[:1], lengths)[0].astype(
            jnp.float32) - want).max())
        read = int((-(-in_use // tile) * tile).sum())
        out[f"mla_decode_{tile}"] = {"ms": ms, "rows_read": read, "gap": gap}
        print(f"mla_decode, tiles of {tile} positions: {ms:.4f} ms a call, "
              f"{read} rows read of {int(in_use.sum())} in use "
              f"({read * 2 * (lat + rope) / ms / 1e6:.1f} GB/s), gap to the "
              f"dense softmax {gap:.5f} (limit {limit:.5f})")
        assert gap <= limit, (tile, gap, limit)
    del leaves
    tokens = list(config["serving"]["prompt_buckets"])      # 8192, 16384
    blocks = ((512, 512), (512, 1024), (1024, 512), (1024, 1024))
    assert (PREFILL_BLOCK, PREFILL_BLOCK) in blocks
    for t in tokens:
        ks = jax.random.split(jax.random.key(t), 3)
        q, k = (jax.random.normal(kk, (1, heads, t, nope + rope),
                                  jnp.bfloat16) for kk in ks[:2])
        v = jax.random.normal(ks[2], (1, heads, t, vd), jnp.bfloat16)
        for bq, bk in blocks:
            fn = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, causal=True, block_q=min(bq, t), block_k=min(bk, t),
                native=True))
            ms = 1e3 * timed(fn, q, k, v, reps=6)
            flops = heads * t * t * (nope + rope + vd)      # causal half
            out[f"flash_fwd_{t}_{bq}x{bk}"] = {"ms": ms}
            print(f"flash_fwd, {t} tokens in tiles of {bq} x {bk}: "
                  f"{ms:.2f} ms ({flops / ms / 1e9:.1f} TFLOP/s causal)")
    # the committed tiles against a dense causal softmax, at 2048 tokens
    # (the scores of 16384 are 34 GB); unit-normal values, bfloat16 results
    n = min(2048, tokens[0])
    q, k, v = (a[:, :, :n] for a in (q, k, v))
    got = flash_attention(q, k, v, causal=True,
                          block_q=min(PREFILL_BLOCK, n),
                          block_k=min(PREFILL_BLOCK, n), native=True)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                    preferred_element_type=jnp.float32) \
        / (nope + rope) ** 0.5
    seen = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    dense = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(seen, sc, -jnp.inf), -1).astype(jnp.bfloat16), v,
        preferred_element_type=jnp.float32)
    gap = float(jnp.abs(got.astype(jnp.float32) - dense).max())
    limit = 2 * 2.0 ** -8 * float(jnp.abs(dense).max())
    out["flash_fwd_gap"] = {"gap": gap, "limit": limit}
    print(f"flash_fwd at {PREFILL_BLOCK} x {PREFILL_BLOCK}, {n} tokens: gap "
          f"to the dense softmax {gap:.5f} (limit {limit:.5f})")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kernel_bench_latent.json", "w") as f:
        json.dump(out, f, indent=1)
    assert gap <= limit
    # the committed tiles are the sweep's best, or within 5 % of it
    best = min(row["ms"] for name, row in out.items()
               if name.startswith("mla_decode_"))
    assert out[f"mla_decode_{committed}"]["ms"] <= 1.05 * best
    for t in tokens:
        best = min(row["ms"] for name, row in out.items()
                   if name.startswith(f"flash_fwd_{t}_"))
        assert out[f"flash_fwd_{t}_{PREFILL_BLOCK}x{PREFILL_BLOCK}"]["ms"] \
            <= 1.05 * best


def test_counts_against_hand_reckoned_values():
    """The published widths (the configuration file as committed)."""
    c = run.load("configs", CONFIG)
    assert (work_latent.layers_of(c), work_latent.dense_layers_of(c)) \
        == (8, 1)
    # a cached position: 512 latent values and 64 rotary lanes of 2 bytes
    assert work_latent.latent_row_bytes(c) == 1152
    # 32 heads x (576 lanes of score + 512 of weighted sum) x 2
    assert work_latent.attention_row_flops(c) == 69_632
    # W_q 2048 x 6144, W_kva 2048 x 576, W_kvb 512 x 8192, W_o 4096 x 2048
    assert work_latent.attention_params(c) \
        == 12_582_912 + 1_179_648 + 4_194_304 + 8_388_608 == 26_345_472
    assert work_latent.expert_params(c) == 4_718_592       # 3 x 2048 x 768
    assert work_latent.shared_params(c) == 9_437_184
    assert work_latent.router_params(c) == 262_144         # 2048 x 128
    assert work_latent.dense_ffn_params(c) == 37_748_736   # 3 x 2048 x 6144
    assert work_latent.head_params(c) == 32_833_536        # 2048 x 16032
    assert work_latent.resident_params(c) \
        == 8 * 26_345_472 + 37_748_736 + 7 * (262_144 + 9_437_184) \
        + 32_833_536 == 349_241_344
    # 48 slots at 9660 rows, 8 layers: 3.71 M rows; 100 experts read
    rows = 48 * 9660 * 8
    assert work_latent.latent_rows_bytes(c, rows) == 4_273_274_880
    assert work_latent.decode_step_bytes(c, rows, 100) \
        == 4_273_274_880 + 2 * (349_241_344 + 100 * 4_718_592)
    # 7 expert layers' router and shared experts, and the 100 experts
    assert work_latent.moe_step_bytes(c, 100) \
        == 2 * (7 * (262_144 + 9_437_184) + 100 * 4_718_592)
    # 7 expert layers x 48 slots x 6 choices, an eighth of them held
    pairs = 7 * 48 * 6 / 8
    assert work_latent.decode_step_flops(c, rows, pairs) \
        == rows * 69_632 + 2 * (48 * 349_241_344 + pairs * 4_718_592)
