"""CPU rehearsal of the short-convolution cell at toy size, run by hand
(not part of tier-1), its controls and seeded draw on the chip, and its
byte and operation counts against values reckoned by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

The family, driver `serve_closed_shortconv` and reader `shortconv_share`
end to end through `run.run_cell`. Nothing here is a measurement: a time
from a CPU run is never a device number."""
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
from benchmarks.families.lfm2_moe_serve import FAULTS  # noqa: E402
from benchmarks.harness import work_shortconv  # noqa: E402
from benchmarks.readers import shortconv_share  # noqa: E402
from benchmarks.tests.test_rehearsal_sparse import _StandIn  # noqa: E402

CELL = "lfm2_24b_serve_longdoc"
CONFIG = "lfm2_24b_a2b_pp5"


@pytest.fixture
def toy():
    """The published model's shapes at toy widths (as tests/test_lfm2_moe.py):
    the published layer pattern's first period, C C A C, both dense layers
    and two expert layers of 8 experts, 2 a token, all held; prompts of
    20-60, so every tail was grafted from a prompt's last real rows."""
    config = copy.deepcopy(run.load("configs", CONFIG))
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=96, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, num_hidden_layers=4, vocab_size=96)
    config["held"].update(experts=[0, 8])
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
        "benchmarks.families.lfm2_moe_serve").build(config, seed)


def _chooser(built, pick, **how):
    """`_StandIn`'s choice of token from the plain reference's logits,
    computed as `how` says (`lower=True`, `fault=` one of `FAULTS`)."""
    return lambda ids, at: pick(built.reference_logits(
        ids, at=at[:, None], **how)[:, 0])


def _driver(built, workload, seed, cache_dir, on_chip):
    driver = importlib.import_module(
        "benchmarks.drivers.serve_closed_shortconv").Driver(
            built, workload, seed, cache_dir, on_chip)
    driver.setup()
    driver.warm()
    return driver


def test_shortconv_cell_end_to_end(toy, tmp_path, capsys):
    import jax
    workload, config = toy
    devices = jax.devices()[:1]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": 1}
    result = run.run_cell(CELL, workload, config, 2**31 + 44, 3.0, False,
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
    assert "attention and expert layers over the window" in out


def test_driver_takes_the_counters_over_the_window(toy, tmp_path):
    workload, config = toy
    driver = _driver(_build(config, 5), workload, 5, str(tmp_path), False)
    assert driver.check()
    driver.measure(2.0)
    steps = driver.context["status_delta"]["steps"]
    delta = driver.context["shortconv_delta"]
    assert steps > 0
    # 1 attention layer x 4 slots of at most the 96-row rung
    assert 0 < delta["global_rows_attended"] <= 1 * 4 * 96 * steps
    # 2 expert layers of 8 held experts, 4 slots x 2 choices a step (the
    # counts reach the host a block after the step count)
    assert 0 < delta["moe_expert_reads"] <= 2 * 8 * (steps + 2)
    assert delta["moe_pairs"] % (2 * 4 * 2) == 0
    assert abs(delta["moe_pairs"] - 2 * 4 * 2 * steps) <= 2 * 4 * 2 * 2
    ctx = {"driver": driver.context, "config": config, "trace": None}
    # no trace (and, on the parent, no counters): nothing to read
    for spec in ({"key": "step_hbm_share"}, {"key": "prefill_moe_mfu"},
                 {"key": "conv_prefill_roofline"},
                 {"key": "moe_hbm_share"},
                 {"key": "scopes_ms", "scopes": ["shortconv"],
                  "program": "superstep"}):
        assert shortconv_share.read(ctx, spec) is None
    ctx["driver"] = {"status_delta": {"steps": 3, "tokens": 9}}
    ctx["trace"] = {"span": (0, 1)}
    for spec in ({"key": "step_hbm_share"}, {"key": "prefill_moe_mfu",
                                              "scopes": ["moe"],
                                              "program": "admit"},
                 {"key": "moe_hbm_share", "scopes": ["moe"],
                  "program": "superstep"},
                 {"key": "conv_prefill_roofline", "scopes": ["shortconv"],
                  "program": "admit"}):
        assert shortconv_share.read(ctx, spec) is None


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
                  - plain).max() > 1e-6


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
    benchmarks/tests/test_rehearsal_shortconv.py` on a TPU): the served
    streams are correct; the streams the reference picks one precision
    below the configuration's (float8 weights, block inputs, tails, cached
    rows, the residual stream) and those of a reference with a fault
    planted (`FAULTS`: the taps one row late, the gates swapped, the expert
    bias in the weights) are each judged by the same `check()`, and every
    verdict is printed. Asserted: the served streams pass, float8 and the
    two faults of the convolution do not (the bias's reading is reported
    in PERF.md, section 6, beside what it takes to see it)."""
    _on_chip_or_skip("the controls run")
    seed = int(os.environ.get("SHORTCONV_CONTROL_SEED", 3_000_004_401))
    workload = run.load("workloads", CELL)
    config = run.load("configs", workload["config"])
    built = _build(config, seed)
    driver = _driver(built, workload, seed, run.CACHE, True)
    srv = driver.srv
    try:
        verdicts = {"served": driver.check()}
        print(driver.notes[-1], flush=True)
        for name, how in [("float8", {"lower": True})] + [
                (fault, {"fault": fault}) for fault in FAULTS]:
            driver.srv = _StandIn(srv, _chooser(
                built, lambda l: l.argmax(-1), **how))
            verdicts[name] = driver.check()
            print(f"{name}: {driver.notes[-1]}", flush=True)
        print(f"seed {seed}: correct by check(): {verdicts}")
        assert verdicts.pop("served") is True
        assert not any(verdicts[name] for name in (
            "float8", "conv_one_late", "gates_swapped")), verdicts
    finally:
        srv.shutdown()


def test_served_gaps_on_the_chip():
    """The served path's largest gap to the reference over more sets of
    weights than one run reads, at the published widths, on the chip only
    (`-k served_gaps`; `SHORTCONV_GAP_SEEDS`, comma-separated, the seeds):
    `check()` over each seed's eight probes, its note printed, so that
    `LOGIT_TOLERANCE` is set from many probe tokens."""
    _on_chip_or_skip("the served gaps are read")
    import gc
    seeds = [int(s) for s in os.environ.get(
        "SHORTCONV_GAP_SEEDS", "3000004402,3000004403").split(",")]
    workload = run.load("workloads", CELL)
    config = run.load("configs", workload["config"])
    for seed in seeds:
        driver = _driver(_build(config, seed), workload, seed, run.CACHE,
                         True)
        try:
            verdict = driver.check()
            print(f"seed {seed}: correct {verdict}; {driver.notes[-1]}",
                  flush=True)
        finally:
            driver.srv.shutdown()
        del driver
        gc.collect()


def test_seeded_draw_on_the_chip():
    """What the seeded weights give at the published widths, on the chip
    only (`-k seeded_draw`): the reference's argmax over the last 512
    positions of a 16384-token prompt, how many are distinct, and how far
    each planted fault and the lower precision move those positions'
    logits (rms and largest): a draw under which `correct` compares
    nothing shows here as few distinct tokens or faults that move
    nothing."""
    _on_chip_or_skip("the seeded draw is read")
    import numpy as np
    config = run.load("configs", CONFIG)
    built = _build(config, 3_000_004_401)
    rng = np.random.default_rng(44)
    ids = rng.integers(1, built.vocab, (1, 16384)).astype(np.int32)
    at = np.arange(16384 - 512, 16384, dtype=np.int32)[None]
    plain = built.reference_logits(ids, at=at)[0]
    top = plain.argmax(-1)
    print(f"seeded draw: {len(set(top.tolist()))} distinct argmax of "
          f"{len(top)} late positions, max |logit| "
          f"{float(np.abs(plain).max()):.3f}, median gap between the two "
          f"best {float(np.median(np.diff(np.sort(plain, -1)[:, -2:]))):.4f}",
          flush=True)
    for name, how in [("float8", {"lower": True})] + [
            (fault, {"fault": fault}) for fault in FAULTS]:
        other = built.reference_logits(ids, at=at, **how)[0]
        moved = other - plain
        rms = float(np.sqrt((moved ** 2).mean()))
        print(f"  {name}: logits moved by {rms:.4f} "
              f"rms, {float(np.abs(moved).max()):.4f} at most; argmax "
              f"changed at {int((other.argmax(-1) != top).sum())} of "
              f"{len(top)}", flush=True)
    assert len(set(top.tolist())) > len(top) // 4


def test_counts_against_hand_reckoned_values():
    """The published widths (the configuration file as committed)."""
    c = run.load("configs", CONFIG)
    assert (work_shortconv.layers_of(c), work_shortconv.dense_layers_of(c),
            work_shortconv.conv_layers_of(c),
            work_shortconv.attention_layers_of(c)) == (8, 2, 6, 2)
    # a cached position: k and v of 8 heads of 64, 2 bytes
    assert work_shortconv.kv_row_bytes(c) == 2048
    # W_in 2048 x 6144, W_out 2048 x 2048, taps 2048 x 3
    assert work_shortconv.conv_params(c) == 12_582_912 + 4_194_304 + 6144
    # W_q, W_o 2048 x 2048; W_k, W_v 2048 x 512
    assert work_shortconv.attention_params(c) == 10_485_760
    assert work_shortconv.expert_params(c) == 9_437_184    # 3 x 2048 x 1536
    assert work_shortconv.router_params(c) == 131_072      # 2048 x 64
    assert work_shortconv.dense_ffn_params(c) == 72_351_744
    assert work_shortconv.head_params(c) == 134_217_728    # 2048 x 65536
    assert work_shortconv.resident_params(c) \
        == 6 * 16_783_360 + 2 * 10_485_760 + 2 * 72_351_744 \
        + 6 * 131_072 + 134_217_728 == 401_379_328
    # 48 slots: 6 tails of 2 x 2048 read and written, 2 bytes
    assert work_shortconv.tail_bytes(c, 48) == 2 * 6 * 48 * 2 * 2048 * 2 \
        == 4_718_592
    # a prompt of 16384 through the 6 mixers: W_in and W_out (8 x 2048^2
    # FLOP a token), the taps and the gate (7 x 2048); the weights once, the
    # input read and the output written once
    assert work_shortconv.prefill_conv_work(c, 16384) == (
        6 * 16384 * (8 * 2048 ** 2 + 7 * 2048),
        2 * 6 * (16_783_360 + 2 * 16384 * 2048))
    assert work_shortconv.moe_step_bytes(c, 366) \
        == 2 * (6 * 131_072 + 366 * 9_437_184)
    # 2 attention layers x 48 slots at 9600 rows in use, 366 expert reads
    rows = 2 * 48 * 9600
    assert work_shortconv.rows_bytes(c, rows) == 1_887_436_800
    assert work_shortconv.decode_step_bytes(c, 48, rows, 366) \
        == 1_887_436_800 + 4_718_592 + 2 * (401_379_328 + 366 * 9_437_184)
    # a prompt of 16384: its 4 pairs a token through 6 expert layers
    assert work_shortconv.prefill_moe_flops(c, 16384) \
        == 16384 * 4 * 6 * 6 * 2048 * 1536
