"""CPU rehearsal of the sparse-attention cell at toy size, run by hand (not
part of tier-1), and its byte counts against values reckoned by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

The family, driver `serve_closed_sparse` and reader `sparse_share` end to
end through `run.run_cell`. Nothing here is a measurement: a time from a
CPU run is never a device number."""
from __future__ import annotations

import copy
import importlib
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import work_sparse  # noqa: E402
from benchmarks.readers import sparse_share  # noqa: E402

CELL = "keye_vl2_serve_longdoc"
CONFIG = "keye_vl2_30b_a3b_ep8"


@pytest.fixture
def toy():
    """Every ratio of the published model at toy widths (as
    tests/test_keye_vl.py): the second of two shares of 16 experts, half
    the vocabulary, `topk` 16 under prompts of 20-60 so that selection
    bites."""
    config = copy.deepcopy(run.load("configs", CONFIG))
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts=8, num_local_experts=8,
        num_experts_per_tok=4, moe_intermediate_size=48,
        num_hidden_layers=2, vocab_size=96)
    config["rope_scaling"]["mrope_section"] = [2, 3, 3]
    config["sa_config"].update(indexer_head_dim=8, indexer_num_heads=4,
                               topk=16)
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
        "benchmarks.families.keye_vl_serve").build(config, seed)


def _driver(built, workload, seed, cache_dir, on_chip):
    driver = importlib.import_module(
        "benchmarks.drivers.serve_closed_sparse").Driver(
            built, workload, seed, cache_dir, on_chip)
    driver.setup()
    driver.warm()
    return driver


def test_sparse_cell_end_to_end(toy, tmp_path, capsys):
    import jax
    workload, config = toy
    devices = jax.devices()[:1]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": 1}
    result = run.run_cell(CELL, workload, config, 2**31 + 35, 3.0, False,
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
    assert "indexer and expert layers over the window" in out


def test_driver_takes_the_counters_over_the_window(toy, tmp_path):
    workload, config = toy
    driver = _driver(_build(config, 5), workload, 5, str(tmp_path), False)
    assert driver.check()
    driver.measure(2.0)
    steps = driver.context["status_delta"]["steps"]
    delta = driver.context["sparse_delta"]
    assert steps > 0
    # 2 layers x 4 slots: at most 16 rows kept of at most 96 scored each
    assert 0 < delta["dsa_rows_selected"] <= 2 * 4 * 16 * steps
    assert delta["dsa_rows_selected"] < delta["dsa_rows_scored"] \
        <= 2 * 4 * 96 * steps
    assert 0 < delta["moe_expert_reads"] <= 2 * 8 * steps
    ctx = {"driver": driver.context, "config": config, "trace": None}
    share = sparse_share.read(ctx, {"key": "selected_share"})
    assert 16.0 < share < 100.0         # contexts of 20-68 against topk 16
    # no trace (and, on the parent, no counters): nothing to read
    assert sparse_share.read(ctx, {"key": "step_hbm_share"}) is None
    assert sparse_share.read(ctx, {
        "key": "scopes_ms", "scopes": ["indexer/score"],
        "program": "superstep"}) is None
    ctx["driver"] = {"status_delta": {"steps": 3, "tokens": 9}}
    assert sparse_share.read(ctx, {"key": "selected_share"}) is None


class _StandIn:
    """A server whose streams are chosen from the plain reference's logits
    by `choose(ids, at) -> (batch,) token at position at[b] of row b`,
    greedy from position to position, and not served: what `check()` makes
    of streams that a lower precision, or a fault, would hand out.
    Everything else is the real server's."""

    def __init__(self, srv, choose):
        self._srv, self._choose = srv, choose
        self._prompts, self._streams = [], None

    def __getattr__(self, name):
        return getattr(self._srv, name)

    def submit(self, prompt, max_new_tokens, **_):
        i = len(self._prompts)
        self._prompts.append(np.asarray(prompt, np.int32))
        return types.SimpleNamespace(
            result=lambda timeout=None: self._all(max_new_tokens)[i])

    def _all(self, n):
        if self._streams is None:
            self._streams = [[] for _ in self._prompts]
            buckets = sorted(self._srv.prompt_buckets)
            groups = {}
            for i, p in enumerate(self._prompts):
                groups.setdefault(next(b for b in buckets if b >= len(p)),
                                  []).append(i)
            for bucket, rows in groups.items():
                ids = np.zeros((len(rows), bucket + n - 1), np.int32)
                lens = np.asarray([len(self._prompts[i]) for i in rows])
                for row, i in zip(ids, rows):
                    row[:len(self._prompts[i])] = self._prompts[i]
                for step in range(n):
                    at = lens - 1 + step
                    tok = self._choose(ids, at)
                    for r, i in enumerate(rows):
                        self._streams[i].append(int(tok[r]))
                    if step < n - 1:
                        ids[np.arange(len(rows)), at + 1] = tok
        return self._streams


def _chooser(built, pick, lower=False):
    return lambda ids, at: pick(built.reference_logits(
        ids, at=at[:, None], lower=lower)[:, 0])


def test_check_refuses_streams_the_reference_ranks_last(toy, tmp_path):
    """`check()` itself says no: the served streams pass, the reference's
    own greedy streams pass, its least likely token at every position does
    not (at toy widths a lower precision moves logits by less than the
    limit; the chip's control is the next test)."""
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


def test_lower_precision_streams_fail_check_on_the_chip():
    """The control of `LOGIT_TOLERANCE`, at the published widths, on the
    chip only (`chiprun -- python3 -m pytest -s -k on_the_chip
    benchmarks/tests/test_rehearsal_sparse.py`): the served streams are
    correct, and the streams the reference picks one precision below the
    configuration's (float8 weights and block inputs, index scores in
    bfloat16 sums) are NOT, by the same `check()`."""
    import jax
    if jax.default_backend() != "tpu":
        pytest.skip("the control runs at the published widths, on a TPU")
    from deeplearning4j_tpu.runtime import executables
    executables.configure_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    seed = 3_000_003_501
    workload = run.load("workloads", CELL)
    config = run.load("configs", workload["config"])
    built = _build(config, seed)
    driver = _driver(built, workload, seed, run.CACHE, True)
    srv = driver.srv
    try:
        served = driver.check()
        driver.srv = _StandIn(srv, _chooser(built, lambda l: l.argmax(-1),
                                            lower=True))
        control = driver.check()
        print("\n".join(driver.notes))
        print(f"seed {seed}: served correct {served}, one precision below "
              f"correct {control}")
        assert served is True and control is False
    finally:
        srv.shutdown()


def test_byte_counts_against_hand_reckoned_values():
    """The published widths (the configuration file as committed)."""
    c = run.load("configs", CONFIG)
    assert work_sparse.layers_of(c) == 8
    # an expert: 3 x 2048 x 768 weights of 2 bytes (4.72 M parameters)
    assert work_sparse.expert_bytes(c) == 9_437_184
    # the router 2048 x 128
    assert work_sparse.router_bytes(c) == 524_288
    # 14 of 16 experts read in each of 8 layers
    assert work_sparse.moe_step_bytes(c, 112) \
        == 8 * 524_288 + 112 * 9_437_184 == 1_061_158_912
    # K and V rows of 4 heads of 128; an index key of 64
    assert work_sparse.kv_row_bytes(c) == 2048
    assert work_sparse.index_key_bytes(c) == 128
    # q 2048 x 4096, k and v 2048 x 512, o 4096 x 2048
    assert work_sparse.attention_weight_bytes(c) == 37_748_736
    # index queries 2048 x 1024, the key 2048 x 64, the weights 2048 x 16
    assert work_sparse.indexer_weight_bytes(c) == 4_521_984
    # 32 slots at 9600 rows, 8 layers: 2.46 M rows scored, 524288 kept
    scored, kept = 32 * 9600 * 8, 32 * 2048 * 8
    assert work_sparse.index_scan_bytes(c, scored) == 314_572_800
    assert work_sparse.selected_row_bytes(c, kept) == 1_073_741_824
    assert work_sparse.decode_step_bytes(c, scored, kept, 112) \
        == 8 * (37_748_736 + 4_521_984) + 314_572_800 + 1_073_741_824 \
        + 1_061_158_912 + 2 * 2048 * 18992
