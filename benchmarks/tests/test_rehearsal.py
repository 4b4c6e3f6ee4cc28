"""CPU rehearsals at toy sizes, run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

Both drivers end to end through `run.run_cell`, the shape of the result,
`correct` turning false when a request is made to fail, and the trace
reduction on a small synthetic list of intervals. Nothing here is a
measurement: a time from a CPU run is never a device number."""
from __future__ import annotations

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import stats, trace  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _toy(kind, name):
    cfg = run.load(kind, name)
    return copy.deepcopy(cfg)


def _cell(name, workload, config, trace_on, tmp_path):
    import jax
    devices = jax.devices()[:1]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": 1}
    return run.run_cell(name, workload, config, 2**31 + 11, 3.0, trace_on,
                        devices, device, cache_dir=str(tmp_path),
                        on_chip=False)


@pytest.fixture
def toy_resnet():
    config = _toy("configs", "resnet50_imagenet")
    config["model"].update(image_size=32, num_classes=10)
    config["assumed"]["batch_per_chip"] = 4
    workload = _toy("workloads", "resnet50_fit_device")
    workload["warmup_steps"] = 4
    return workload, config


@pytest.fixture
def toy_bert():
    config = _toy("configs", "bert_base_serve")
    config["model"].update(vocab_size=128, hidden_size=32,
                           num_hidden_layers=2, num_attention_heads=2,
                           intermediate_size=64, max_position_embeddings=64)
    config["serving"].update(slots=4, cache_lengths=[64],
                             prompt_buckets=[16, 32])
    workload = _toy("workloads", "bert_serve_decode")
    workload.update(clients=4, warmup_seconds=0.5)
    workload["requests"].update(
        distinct=16, prompt_len={"dist": "log_uniform", "lo": 4, "hi": 16},
        output_len={"dist": "log_uniform", "lo": 4, "hi": 8})
    return workload, config


@pytest.mark.parametrize("data", ["device", "host"])
def test_fit_driver_end_to_end(toy_resnet, data, tmp_path):
    workload, config = toy_resnet
    workload["data"] = data
    result = _cell("resnet50_fit_device", workload, config, False, tmp_path)
    assert set(result) == RESULT_KEYS
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert result["metrics"]["train_samples_per_s"]["value"] > 0
    json.dumps(result)
    # `correct` follows the losses, which a toy net on the CPU does not
    # promise (see the driver); it has only to be a bool here
    assert isinstance(result["correct"], bool)


def test_serve_driver_end_to_end(toy_bert, tmp_path):
    workload, config = toy_bert
    result = _cell("bert_serve_decode", workload, config, False, tmp_path)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                      "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_serve_driver_failed_request_is_not_correct(toy_bert, tmp_path):
    workload, config = toy_bert
    workload["requests"]["prompt_len"] = {"dist": "fixed", "lo": 40,
                                          "hi": 40}
    config["serving"]["prompt_buckets"] = [16, 64]
    workload["requests"]["output_len"] = {"dist": "fixed", "lo": 30, "hi": 30}
    # prompt 40 + 30 new tokens overflow the 64-row cache: every request
    # of the window is refused
    result = _cell("bert_serve_decode", workload, config, False, tmp_path)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] > 0


def test_traced_run_off_the_chip_has_no_device_line(toy_bert, tmp_path):
    workload, config = toy_bert
    with pytest.raises(RuntimeError, match="no device operation"):
        _cell("bert_serve_decode", workload, config, True, tmp_path)


def test_interval_arithmetic():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert stats.union(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert stats.covered(iv) == pytest.approx(3.0)
    assert stats.idle_share(iv, 0.0, 5.0) == pytest.approx(40.0)
    assert stats.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == pytest.approx(95)


def test_trace_reduction_on_synthetic_intervals():
    ops = {0: [("fusion.1", 1.0, 1.4), ("fusion.2", 1.4, 1.5),
               ("fusion.1", 2.0, 2.4), ("fusion.2", 2.4, 2.5),
               ("fusion.1", 3.0, 3.4), ("fusion.2", 3.4, 3.5)]}
    modules = {0: [("jit_step(1)", 1.0, 1.5), ("jit_step(1)", 2.0, 2.5),
                   ("jit_step(1)", 3.0, 3.5)]}
    notes = [(trace.SPAN, 0.5, 4.0), (trace.PREFIX + "fit", 0.6, 3.9),
             (trace.PREFIX + "iterator.next", 1.6, 1.9)]
    s = trace.summarise(ops, modules, notes)
    assert s["span"] == (1.0, 3.5)       # clipped to where the device ran
    assert s["busy_s"] == pytest.approx(1.5)
    assert s["idle_share"] == pytest.approx(40.0)
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(1.2)]
    assert [g[0] for g in s["idle_gaps"]] == ["iterator.next", "fit"]
    p = s["programs"]["jit_step(1)"]
    assert p["count"] == 3 and p["busy_between"] == pytest.approx(1.0)
    assert trace.summarise({}, {}, notes) is None


def test_programs_told_apart_by_marks():
    from benchmarks.readers import trace_reduce
    ops = {0: [("op", 0.0, 10.0)]}
    modules = {0: [("jit_run(1)", 1.0, 1.3), ("jit_run(2)", 1.4, 1.5),
                   ("jit_run(1)", 2.0, 2.3), ("jit_run(3)", 2.31, 2.32),
                   ("jit_run(2)", 3.0, 3.1), ("jit_run(1)", 4.0, 4.3)]}
    marks = [(trace.AFTER + "admit", 1.51, 1.51),
             (trace.AFTER + "admit", 3.11, 3.11)]
    tr = trace.summarise(ops, modules, marks)
    ctx = {"trace": tr, "driver": {"marks": ["admit"]}}
    admit = trace_reduce.read(ctx, {"key": "program_ms", "role": "admit"})
    decode = trace_reduce.read(ctx, {"key": "program_ms",
                                     "heaviest_without": ["admit"]})
    assert admit == pytest.approx(100.0) and decode == pytest.approx(300.0)
    # no mark in the span: no admit ran in it, IF the driver writes marks
    bare = {"trace": trace.summarise(ops, modules, []),
            "driver": {"marks": ["admit"]}}
    assert trace_reduce.read(bare, {"key": "program_ms",
                                    "role": "admit"}) is None
    assert trace_reduce.read(bare, {
        "key": "program_ms", "heaviest_without": ["admit"]}) \
        == pytest.approx(300.0)
    bare["driver"] = {}
    assert trace_reduce.read(bare, {"key": "program_ms",
                                    "heaviest_without": ["admit"]}) is None
