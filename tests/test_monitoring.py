"""Monitoring subsystem (metrics registry + span tracing) and its wiring
through trainers, the parallel stack, the executioner, and the UI server
— plus the round-5 satellite regressions that shipped with it."""
import json
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import monitoring as mon
from deeplearning4j_tpu.datasets import ArrayDataSetIterator, DataSet
from deeplearning4j_tpu.monitoring.registry import MetricsRegistry
from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                   NeuralNetConfiguration, OutputLayer, Sgd)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


@pytest.fixture(autouse=True)
def _monitoring_off_after():
    """Every test leaves monitoring disabled and the tracer empty —
    the flag is process-global and later test modules must keep the
    zero-overhead fast path."""
    yield
    mon.disable()
    mon.get_tracer().clear()


def _mlp(n_in=4, n_out=2, seed=1):
    conf = (NeuralNetConfiguration.Builder()
            .seed(seed).updater(Sgd(0.1)).activation("relu")
            .list()
            .layer(DenseLayer.Builder().nOut(8).build())
            .layer(OutputLayer.Builder("mcxent").nOut(n_out)
                   .activation("softmax").build())
            .setInputType(InputType.feedForward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=16, n_in=4, n_out=2, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, n)]
    return x, y


# -- registry semantics ----------------------------------------------------
def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("req.total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("req.total") is c          # get-or-create
    g = reg.gauge("queue.depth")
    g.set(3)
    g.inc()
    g.dec(0.5)
    assert g.value == pytest.approx(3.5)


def test_histogram_quantiles_and_snapshot():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in range(1, 101):        # 1..100
        h.observe(v)
    assert h.count == 100 and h.sum == pytest.approx(5050)
    assert h.quantile(0.5) == pytest.approx(50, abs=1)
    assert h.quantile(0.95) == pytest.approx(95, abs=1)
    assert h.quantile(0.99) == pytest.approx(99, abs=1)
    snap = h.snapshot()
    assert snap["min"] == 1 and snap["max"] == 100
    assert snap["p50"] and snap["p95"] and snap["p99"]
    # snapshot must be JSON-native (same idiom as ui/stats records)
    json.dumps(reg.snapshot())


def test_histogram_reservoir_bounded():
    reg = MetricsRegistry()
    h = reg.histogram("lat", reservoir=64)
    for v in range(10_000):
        h.observe(float(v))
    assert h.count == 10_000                       # exact count kept
    assert len(h._ring) == 64                      # memory bounded
    # quantiles reflect the recent window, not all history
    assert h.quantile(0.5) > 9_000


def test_labels_make_distinct_children_and_kind_conflict_raises():
    reg = MetricsRegistry()
    a = reg.counter("hits", labels={"route": "/a"})
    b = reg.counter("hits", labels={"route": "/b"})
    a.inc(2)
    b.inc(3)
    assert a is not b and a.value == 2 and b.value == 3
    with pytest.raises(TypeError):
        reg.gauge("hits", labels={"route": "/a"})
    assert reg.get("hits", labels={"route": "/a"}) is a
    assert reg.get("nope") is None


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("dl4j.test.count", help="a counter").inc(7)
    reg.gauge("dl4j.test.gauge", labels={"device": "cpu:0"}).set(1.5)
    h = reg.histogram("dl4j.test.lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    text = reg.prometheus_text()
    assert "# TYPE dl4j_test_count counter" in text
    assert "dl4j_test_count 7" in text
    assert '# HELP dl4j_test_count a counter' in text
    assert 'dl4j_test_gauge{device="cpu:0"} 1.5' in text
    assert "# TYPE dl4j_test_lat summary" in text
    assert 'dl4j_test_lat{quantile="0.5"}' in text
    assert "dl4j_test_lat_count 4" in text
    assert "dl4j_test_lat_sum 10" in text
    # every sample line is NAME{LABELS}? VALUE
    import re
    sample = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? \S+$")
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert sample.match(line), line


# -- disabled fast path ----------------------------------------------------
def test_disabled_span_is_shared_noop_singleton():
    """The disabled contract since ISSUE 26: a span is ONE profiler
    annotation (a flag test in C++ outside a profiler session) that
    records nothing in the Tracer — not a Span, no lock, no event."""
    mon.disable()
    s1 = mon.span("a")
    s2 = mon.span("b", step=3)                     # values ride along
    assert not isinstance(s1, mon.Span) and type(s1) is type(s2)
    with s1:
        with s2 as inner:
            inner.set_metadata(tokens=1)           # same surface as Span
    assert mon.get_tracer().events() == []
    assert mon.get_tracer().current_stack() == []


def test_disabled_traced_iter_and_transfer_are_noops():
    mon.disable()
    data = [1, 2, 3]
    # same items, one annotation per pull, nothing in the Tracer
    assert list(mon.traced_iter(data)) == data
    assert mon.get_tracer().events() == []
    reg = MetricsRegistry()
    mon.record_transfer(1 << 20, registry=reg)
    assert reg.get(mon.TRANSFER_H2D_BYTES) is None  # nothing created


# -- span tracing + Chrome trace export ------------------------------------
def test_span_nesting_and_chrome_trace_json(tmp_path):
    mon.enable()
    mon.get_tracer().clear()
    with mon.span("outer"):
        with mon.span("inner"):
            pass
        with mon.span("inner2"):
            pass
    path = str(tmp_path / "trace.json")
    mon.export_chrome_trace(path)
    with open(path) as f:
        doc = json.loads(f.read())                 # valid JSON
    # the document leads with process/thread-name metadata (ISSUE 15:
    # merged multi-process traces render as separate named lanes)
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert metas and metas[0]["name"] == "process_name"
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert [e["name"] for e in evs] == ["inner", "inner2", "outer"]
    for e in evs:
        assert e["ph"] == "X"
        assert set(e) >= {"name", "ts", "dur", "pid", "tid", "args"}
    outer = evs[-1]
    for child in evs[:-1]:
        assert outer["ts"] <= child["ts"]          # time containment =
        assert (outer["ts"] + outer["dur"]         # chrome nesting
                >= child["ts"] + child["dur"])
        assert child["args"]["depth"] == 1
    assert outer["args"]["depth"] == 0


def test_tracer_event_cap():
    from deeplearning4j_tpu.monitoring.tracing import Tracer
    tr = Tracer(max_events=5)
    mon.enable()
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 5
    assert tr.to_chrome_trace()["otherData"]["droppedEvents"] == 5


def test_fit_exports_nested_dispatch_and_listener_spans(tmp_path):
    """Acceptance: span-traced fit() → Chrome trace JSON with nested
    dispatch/listener phase events."""
    from deeplearning4j_tpu.optimize.listeners import MetricsListener
    net = _mlp()
    net.setListeners(MetricsListener())            # one-line opt-in
    x, y = _data()
    mon.get_tracer().clear()
    for _ in range(3):
        net.fit(DataSet(x, y))
    it = ArrayDataSetIterator(x, y, batch_size=8)
    net.fit(it, epochs=1)
    path = str(tmp_path / "fit_trace.json")
    mon.export_chrome_trace(path)
    with open(path) as f:
        doc = json.loads(f.read())
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"fit", "fit.epoch", "fit.data_next", "train.dispatch",
            "train.listeners"} <= names
    fit_ev = next(e for e in evs if e["name"] == "fit")
    for phase in ("train.dispatch", "train.listeners"):
        ch = next(e for e in evs if e["name"] == phase)
        assert fit_ev["ts"] <= ch["ts"]
        assert fit_ev["ts"] + fit_ev["dur"] >= ch["ts"] + ch["dur"]
        assert ch["args"]["depth"] > fit_ev["args"]["depth"]


# -- executioner jit-cache events -----------------------------------------
def test_executioner_records_jit_cache_miss_metrics():
    from deeplearning4j_tpu.runtime.executioner import OpExecutioner
    mon.enable()
    reg = mon.get_registry()
    misses0 = reg.counter(mon.JIT_CACHE_MISSES).value
    h = reg.histogram(mon.JIT_COMPILE_SECONDS)
    count0 = h.count
    ex = OpExecutioner()                           # fresh cache

    def _mon_test_fn(a):
        return a * 2 + 1

    out = ex.exec(_mon_test_fn, jnp.ones(4))
    np.testing.assert_allclose(np.asarray(out), np.full(4, 3.0))
    assert reg.counter(mon.JIT_CACHE_MISSES).value == misses0 + 1
    assert h.count == count0 + 1
    ex.exec(_mon_test_fn, jnp.ones(4))             # cache hit
    assert reg.counter(mon.JIT_CACHE_MISSES).value == misses0 + 1
    assert h.count == count0 + 1

    # registry.clear() must not orphan the cached handles: the next
    # dispatch re-resolves and the series reappear in the registry
    reg.clear()

    def _mon_test_fn2(a):
        return a - 1

    ex.exec(_mon_test_fn2, jnp.ones(4))
    assert reg.counter(mon.JIT_CACHE_MISSES).value == 1
    assert reg.histogram(mon.JIT_COMPILE_SECONDS).count == 1


# -- /metrics endpoint -----------------------------------------------------
def test_metrics_endpoint_serves_prometheus_text():
    """Acceptance: GET /metrics returns Prometheus text including the jit
    compile-time histogram and device memory gauges."""
    from deeplearning4j_tpu.ui.server import UIServer
    mon.enable()
    server = UIServer.getInstance()
    server.start(port=0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        resp = urllib.request.urlopen(base + "/metrics", timeout=10)
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
        assert "# TYPE dl4j_jit_compile_seconds summary" in text
        assert "dl4j_jit_compile_seconds_count" in text
        assert "# TYPE dl4j_device_memory_bytes gauge" in text
        assert 'dl4j_device_memory_bytes{device="' in text
        assert "dl4j_jit_cache_misses" in text
        # dashboard page carries the metrics tab
        html = urllib.request.urlopen(base + "/", timeout=10).read().decode()
        assert "/metrics" in html and 'id="metrics"' in html
        # disabled scrape still serves (whatever the registry holds)
        # without touching the collectors
        mon.disable()
        resp = urllib.request.urlopen(base + "/metrics", timeout=10)
        assert resp.status == 200
    finally:
        server.stop()


def test_metrics_listener_feeds_registry():
    from deeplearning4j_tpu.optimize.listeners import MetricsListener
    reg = MetricsRegistry()
    net = _mlp(seed=3)
    net.setListeners(MetricsListener(registry=reg,
                                     deviceMemoryFrequency=2))
    x, y = _data(seed=3)
    for _ in range(4):
        net.fit(DataSet(x, y))
    assert reg.counter("dl4j.train.iterations").value == 4
    assert np.isfinite(reg.gauge("dl4j.train.score").value)
    assert reg.histogram("dl4j.train.iteration_seconds").count == 3
    assert reg.get(mon.DEVICE_MEMORY_BYTES,
                   labels={"device": str(jax.devices()[0]),
                           "stat": "bytes_in_use"}) is not None


def test_metrics_listener_iteration_time_dedups_scanned_dispatch():
    """stepsPerDispatch=k fires k iterationDone calls per real update —
    the interval histogram must time dispatch-to-dispatch, not record
    k-1 near-zero samples."""
    from deeplearning4j_tpu.optimize.listeners import MetricsListener
    reg = MetricsRegistry()
    net = _mlp(seed=8)
    net.setListeners(MetricsListener(registry=reg))
    x, y = _data(n=64, seed=8)
    it = ArrayDataSetIterator(x, y, batch_size=16)     # 4 batches
    net.fit(it, epochs=1, stepsPerDispatch=2)          # 2 real updates
    assert reg.counter("dl4j.train.iterations").value == 4
    assert reg.histogram("dl4j.train.iteration_seconds").count == 1


# -- satellite regressions -------------------------------------------------
def test_wrapper_fit_dataset_bumps_params_version(devices8):
    """ADVICE r5 wrapper.py:200: the wrapper's per-batch step must mark
    real param updates for StatsListener's dedup."""
    from deeplearning4j_tpu.parallel import ParallelWrapper
    net = _mlp(n_in=8, seed=5)
    x, y = _data(n=32, n_in=8, seed=5)
    it = ArrayDataSetIterator(x, y, batch_size=16)
    pw = ParallelWrapper.Builder(net).build()
    pw.fit(it, epochs=1)
    assert getattr(net, "_params_version", 0) == 2     # 2 batches
    assert net._last_features is not None
    assert net._last_features.shape == (16, 8)


def test_wrapper_scanned_dispatch_version_and_stats_dedup(devices8):
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.ui.stats import (InMemoryStatsStorage,
                                             StatsListener)
    net = _mlp(n_in=8, seed=6)
    storage = InMemoryStatsStorage()
    net.setListeners(StatsListener(storage, frequency=1,
                                   collectActivations=False))
    x, y = _data(n=64, n_in=8, seed=6)
    it = ArrayDataSetIterator(x, y, batch_size=16)     # 4 batches
    pw = ParallelWrapper.Builder(net).build()
    pw.fit(it, epochs=1, stepsPerDispatch=2)           # 2 scanned groups
    assert net._iteration == 4
    assert net._params_version == 2                    # once per dispatch
    assert net._last_features.shape == (16, 8)         # last real batch
    recs = storage.all()
    assert len(recs) == 4
    # dedup: ratios recorded once per REAL update, not per listener call
    assert sum(1 for r in recs if "updateRatios" in r) == 2


def test_scan_sig_features_none_is_non_scannable():
    """ADVICE r5 wrapper.py:191: features=None must mean 'not scannable',
    not a TypeError on s[0][0]."""
    from deeplearning4j_tpu.parallel import ParallelWrapper
    ds = DataSet(None, np.ones((8, 2), np.float32))
    assert ParallelWrapper._scan_sig(ds) is None


def test_samediff_values_only_checkpoint_restores_updater(tmp_path):
    """ADVICE r5 graph_serde.py:425: values_only=True + save_updater=True
    must round-trip optimizer state through load_values."""
    from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
    from deeplearning4j_tpu.nn import Adam

    def build():
        sd = SameDiff.create()
        x = sd.placeHolder("x", (None, 3))
        labels = sd.placeHolder("labels", (None, 1))
        w = sd.var("w", np.zeros((3, 1), np.float32))
        b = sd.var("b", np.zeros((1,), np.float32))
        pred = x.mmul(w).add(b)
        sd.loss.meanSquaredError("loss", labels, pred)
        sd.setLossVariables("loss")
        sd.setTrainingConfig(TrainingConfig.Builder()
                             .updater(Adam(0.05))
                             .dataSetFeatureMapping("x")
                             .dataSetLabelMapping("labels")
                             .build())
        return sd

    rng = np.random.default_rng(0)
    xs = rng.standard_normal((32, 3)).astype(np.float32)
    ys = (xs @ np.array([[1.0], [-2.0], [0.5]], np.float32))
    ds = DataSet(xs, ys)

    sd = build()
    for _ in range(5):
        sd.fit(ds)
    orig_leaves = [np.asarray(l) for l in
                   jax.tree_util.tree_leaves(sd._opt_state)]
    assert any(np.any(l != 0) for l in orig_leaves)    # momenta are live
    path = str(tmp_path / "ckpt.zip")
    sd.save(path, values_only=True, save_updater=True)

    # fresh graph, no optimizer yet: leaves parked for _ensure_optimizer
    sd2 = build()
    sd2.load_values(path)
    pending = [np.asarray(l) for l in sd2._pending_opt_leaves]
    assert len(pending) == len(orig_leaves)
    for a, b in zip(pending, orig_leaves):
        np.testing.assert_array_equal(a, b)
    # resuming is bit-identical to continuing the original
    want = sd.fit(ds)
    got = sd2.fit(ds)
    assert got == pytest.approx(want, rel=1e-6)
    np.testing.assert_allclose(
        sd2.getVariable("w").getArr().numpy(),
        sd.getVariable("w").getArr().numpy(), rtol=1e-6)

    # live-optimizer graph: leaves spliced directly on load
    sd3 = build()
    sd3.fit(ds)                                        # diverged state
    sd3.load_values(path)
    for a, b in zip(jax.tree_util.tree_leaves(sd3._opt_state),
                    orig_leaves):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_accepts_explicit_mask_rejects_catchalls():
    """ADVICE r5 bert.py:167: *args/**kwargs catch-alls must not pass the
    mask-arity guard, and the guard reports the calling convention the
    impl is actually reachable by."""
    from deeplearning4j_tpu.util.introspect import (accepts_explicit_mask,
                                                    explicit_mask_param)
    # a named mask param is preferred (and bound) BY KEYWORD — never
    # mis-bound to an earlier defaulted positional like causal
    assert explicit_mask_param(
        lambda q, k, v, mask: None, positional_slot=4) \
        == ("keyword", "mask")
    assert explicit_mask_param(
        lambda q, k, v, causal=False, mask=None: None,
        positional_slot=4) == ("keyword", "mask")
    # required 4th positional with a non-reserved name: positional slot
    assert explicit_mask_param(
        lambda q, k, v, extra: None, positional_slot=4) \
        == ("positional", None)
    # DEFAULTED non-mask 4th positional: rejected, not silently bound
    assert explicit_mask_param(
        lambda q, k, v, causal=False: None, positional_slot=4) is None
    # keyword-only mask: reachable, but only BY KEYWORD
    assert explicit_mask_param(
        lambda q, k, v, *, mask=None: None, positional_slot=4) \
        == ("keyword", "mask")
    assert explicit_mask_param(
        lambda q, k, v, **kw: None, positional_slot=4) is None
    assert explicit_mask_param(
        lambda q, k, v, *args: None, positional_slot=4) is None
    assert explicit_mask_param(
        lambda q, k, v, *, kv_mask=None: None, names=("kv_mask",)) \
        == ("keyword", "kv_mask")
    assert explicit_mask_param(
        lambda q, k, v, **kw: None, names=("kv_mask",)) is None

    # positional-only param sharing the name is NOT keyword-reachable
    def posonly(q, k, v, kv_mask, /):
        return None

    assert explicit_mask_param(posonly, names=("kv_mask",)) is None
    assert accepts_explicit_mask(
        lambda q, k, v, **kw: None, min_positional=4) is False
    # un-introspectable callable (a builtin with no signature; np.add
    # has one on the installed numpy) -> None: unknown, not a refusal
    assert accepts_explicit_mask(max, min_positional=4) is None


def test_bert_kwargs_swallowing_attn_impl_rejected():
    from deeplearning4j_tpu.models.bert import (bert_tiny,
                                                classification_loss,
                                                init_bert_params)
    from deeplearning4j_tpu.parallel.ring_attention import dense_attention
    cfg = bert_tiny(max_position_embeddings=16)
    params = init_bert_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (2, 16)),
             "labels": rng.integers(0, cfg.num_labels, (2,)),
             "attention_mask": (np.arange(16)[None, :] < 10
                                ).astype(np.float32).repeat(2, 0)}

    def swallower(q, k, v, **kwargs):   # silently ignores the mask
        return dense_attention(q, k, v)

    with pytest.raises(ValueError, match="mask"):
        classification_loss(cfg, params, batch, train=False,
                            attn_impl=swallower)
    # an impl that DOES declare the mask still works
    def masked(q, k, v, mask):
        return dense_attention(q, k, v,
                               mask=mask[:, None, None, :] > 0)

    loss = classification_loss(cfg, params, batch, train=False,
                               attn_impl=masked)
    assert np.isfinite(float(loss))

    # keyword-only mask: the guard routes the call by keyword instead of
    # rejecting (or crashing with a positional-arity TypeError)
    def masked_kw(q, k, v, *, mask=None):
        return dense_attention(q, k, v,
                               mask=mask[:, None, None, :] > 0)

    loss_kw = classification_loss(cfg, params, batch, train=False,
                                  attn_impl=masked_kw)
    np.testing.assert_allclose(float(loss_kw), float(loss), rtol=1e-6)
