"""CPU rehearsal of the hybrid cell at toy size, run by hand (not part of
tier-1), and the hybrid's byte counts against values reckoned by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

The new family, driver and readers end to end through `run.run_cell`.
Nothing here is a measurement: a time from a CPU run is never a device
number."""
from __future__ import annotations

import copy
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import work_hybrid  # noqa: E402
from benchmarks.readers import hybrid_share  # noqa: E402

CELL = "nemotron3_super_serve_decode"


@pytest.fixture
def toy():
    """Every ratio of the published model at toy widths (as
    tests/test_nemotron_h.py): 2 of 16 experts' shares (8 held), half the
    vocabulary."""
    config = copy.deepcopy(run.load("configs", "nemotron3_super_120b_ep4"))
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
        ssm_state_size=16, chunk_size=8, n_routed_experts=8,
        num_experts_per_tok=4, moe_latent_size=32, moe_intermediate_size=84,
        moe_shared_expert_intermediate_size=84, num_hidden_layers=5,
        vocab_size=96)
    config["published"].update(n_routed_experts=16, vocab_size=192)
    config["held"].update(pattern="*EMEM", experts=[8, 16])
    config["serving"].update(dtype="float32", slots=4, cache_lengths=[64],
                             prompt_buckets=[16, 32])
    workload = copy.deepcopy(run.load("workloads", CELL))
    workload.update(clients=4, warmup_seconds=0.5)
    workload["requests"].update(
        distinct=16, prompt_len={"dist": "log_uniform", "lo": 4, "hi": 24},
        output_len={"dist": "log_uniform", "lo": 4, "hi": 8})
    return workload, config


def _cell(workload, config, trace_on, tmp_path):
    import jax
    devices = jax.devices()[:1]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": 1}
    return run.run_cell(CELL, workload, config, 2**31 + 28, 3.0, trace_on,
                        devices, device, cache_dir=str(tmp_path),
                        on_chip=False)


def test_hybrid_cell_end_to_end(toy, tmp_path, capsys):
    workload, config = toy
    result = _cell(workload, config, False, tmp_path)
    assert result["correct"] is True, capsys.readouterr().out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                      "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    out = capsys.readouterr().out
    assert "gaps to the reference's largest logit" in out
    assert "8 probes at once" in out


def test_hybrid_driver_takes_the_counters_over_the_window(toy, tmp_path):
    import importlib
    workload, config = toy
    built = importlib.import_module(
        "benchmarks.families.nemotron_h_serve").build(config, 5)
    driver = _driver(built, workload, 5, str(tmp_path), False)
    assert driver.check()
    driver.measure(2.0)
    assert driver.context["status_delta"]["steps"] > 0
    steps = driver.context["status_delta"]["steps"]
    delta = driver.context["moe_delta"]
    # 2 expert layers x 4 slots x 4 choices a step, half of them held on
    # average: the exact count is the device's
    assert 0 < delta["moe_pairs"] <= 32 * steps
    assert 0 < delta["moe_expert_reads"] <= 16 * steps
    ctx = {"driver": driver.context, "config": config, "trace": None}
    ratio = hybrid_share.read(ctx, {"key": "load_max_over_mean"})
    assert 1.0 <= ratio <= 8.0
    # no trace (and, on the parent, no counters): nothing to read
    assert hybrid_share.read(ctx, {"key": "step_hbm_share"}) is None
    ctx["driver"] = {"status_delta": {"steps": 3, "tokens": 9}}
    assert hybrid_share.read(ctx, {"key": "load_max_over_mean"}) is None
    assert hybrid_share.read(ctx, {"key": "scope_hbm_share", "scope": "moe",
                                   "program": "superstep"}) is None


def test_every_seed_serves_the_shapes_in_one_order(toy, tmp_path):
    """The seed makes the prompts' ids; the order of (prompt length, output
    length) pairs and of the sampling methods is one for every seed."""
    import importlib
    workload, config = toy
    built = importlib.import_module(
        "benchmarks.families.nemotron_h_serve").build(config, 5)
    lists = []
    for seed in (5, 2**31 + 77):
        driver = _driver(built, workload, seed, str(tmp_path), False)
        driver.srv.shutdown()
        lists.append(driver.requests)
    a, b = lists
    shape = [(len(r["prompt"]), r["max_new_tokens"], r["kw"]["method"])
             for r in a]
    assert shape == [(len(r["prompt"]), r["max_new_tokens"],
                      r["kw"]["method"]) for r in b]
    assert len(a) == workload["requests"]["distinct"]
    assert [m for _, _, m in shape[:4]] == ["greedy", "sample"] * 2
    assert len(set(shape)) > 8                  # a mix, not one shape
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))


class _StandIn:
    """A server whose streams are chosen from the plain reference's logits
    by `choose(ids) -> (batch, time) token at every position`, greedy from
    position to position, and not served: what `check()` makes of streams
    that a lower precision, or a fault, would hand out. Everything else is
    the real server's."""

    def __init__(self, srv, choose):
        self._srv, self._choose = srv, choose
        self._prompts, self._streams = [], None

    def __getattr__(self, name):
        return getattr(self._srv, name)

    def submit(self, prompt, max_new_tokens, **_):
        import types
        i = len(self._prompts)
        self._prompts.append(np.asarray(prompt, np.int32))
        return types.SimpleNamespace(
            result=lambda timeout=None: self._all(max_new_tokens)[i])

    def _all(self, n):
        if self._streams is None:
            lens = [len(p) for p in self._prompts]
            ids = np.zeros((len(lens), max(lens) + n - 1), np.int32)
            for row, p in zip(ids, self._prompts):
                row[:len(p)] = p
            rows = np.arange(len(lens))
            streams = np.zeros((len(lens), n), np.int32)
            for i in range(n):
                at = np.asarray(lens) - 1 + i
                streams[:, i] = self._choose(ids)[rows, at]
                if i < n - 1:
                    ids[rows, at + 1] = streams[:, i]
            self._streams = [list(map(int, s)) for s in streams]
        return self._streams


def _driver(built, workload, seed, cache_dir, on_chip):
    import importlib
    driver = importlib.import_module(
        "benchmarks.drivers.serve_closed_hybrid").Driver(
            built, workload, seed, cache_dir, on_chip)
    driver.setup()
    driver.warm()
    return driver


def test_check_refuses_streams_the_reference_ranks_last(toy, tmp_path):
    """`check()` itself says no: the served streams pass, the reference's
    least likely token at every position does not (at toy widths a lower
    precision moves logits by less than the limit; the chip's control is
    the next test)."""
    import importlib
    workload, config = toy
    built = importlib.import_module(
        "benchmarks.families.nemotron_h_serve").build(config, 7)
    driver = _driver(built, workload, 7, str(tmp_path), False)
    try:
        assert driver.check() is True
        driver.srv = _StandIn(
            driver.srv, lambda ids: built.reference_logits(ids).argmin(-1))
        assert driver.check() is False
        assert "8 probes at once" in driver.notes[-1]
    finally:
        driver.srv.shutdown()


def test_lower_precision_streams_fail_check_on_the_chip():
    """The control of `LOGIT_TOLERANCE`, at the published widths, on the
    chip only (`chiprun -- python3 -m pytest -s -k on_the_chip
    benchmarks/tests/test_rehearsal_hybrid.py`): the served streams are
    correct, and the streams the reference picks one precision below the
    configuration's are NOT, by the same `check()`."""
    import importlib
    import jax
    if jax.default_backend() != "tpu":
        pytest.skip("the control runs at the published widths, on a TPU")
    from deeplearning4j_tpu.runtime import executables
    executables.configure_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    seed = 3_000_002_801
    workload = run.load("workloads", CELL)
    config = run.load("configs", workload["config"])
    built = importlib.import_module(
        "benchmarks.families.nemotron_h_serve").build(config, seed)
    driver = _driver(built, workload, seed, run.CACHE, True)
    try:
        served = driver.check()
        driver.srv = _StandIn(driver.srv, lambda ids: built.reference_logits(
            ids, lower=True).argmax(-1))
        control = driver.check()
        print("\n".join(driver.notes))
        print(f"seed {seed}: served correct {served}, one precision below "
              f"correct {control}")
        assert served is True and control is False
    finally:
        driver.srv.shutdown()


def test_byte_counts_against_hand_reckoned_values():
    """The published widths (the configuration file as committed)."""
    c = run.load("configs", "nemotron3_super_120b_ep4")
    assert work_hybrid.layers_of(c, "E") == work_hybrid.layers_of(c, "M") \
        == 5 and work_hybrid.layers_of(c, "*") == 1
    # an expert: 2 x 1024 x 2688 weights of 2 bytes (5.505 M parameters)
    assert work_hybrid.expert_bytes(c) == 11_010_048
    # router 4096 x 512, two latent projections 4096 x 1024, the shared
    # expert 2 x 4096 x 5376: 54.53 M parameters of 2 bytes
    assert work_hybrid.moe_fixed_bytes(c) == 109_051_904
    # all 128 experts of all 5 layers read: 7.05 GB of experts
    assert work_hybrid.moe_step_bytes(c, 640) \
        == 5 * 109_051_904 + 640 * 11_010_048 == 7_591_690_240
    # state 128 slots x 128 x 64 x 128 x 4 bytes read and written; tails
    # 128 x 3 x 10240 x 2 bytes twice; in_proj 4096 x 18560, out_proj
    # 8192 x 4096, the convolution 10240 x 4
    assert work_hybrid.ssm_layer_bytes(c, 128) \
        == 1_073_741_824 + 15_728_640 + 219_234_304
    assert work_hybrid.ssm_step_bytes(c, 128) == 6_543_523_840
    assert work_hybrid.kv_bytes_per_position(c) == 1024
    # the decode kernel reads 128 slots' whole 1024-row rung of it
    assert hybrid_share.read(
        {"config": c, "trace": None, "driver": {}},
        {"key": "kernel_roofline", "scope": "flash_decode",
         "program": "superstep"}) is None
    assert work_hybrid.attention_weight_bytes(c) == 71_303_168
    assert work_hybrid.decode_step_bytes(c, 128, 300.0, 640) \
        == 7_591_690_240 + 6_543_523_840 + 71_303_168 \
        + 128 * 300 * 1024 + 268_435_456
