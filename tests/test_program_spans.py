"""The program's spans in `jax.profiler`'s trace, and the names on what the
device runs (ISSUE 26): a profiler session around a toy `fit()` and a toy
`GenerationServer` holds the `dl4j.*` spans with their stats, and holds
none when no session was on; compiled modules carry the registered names
(`jit_superstep`, `jit_admit`, the named train steps) and no `jit_run`; the
lowered decode step carries the stage scopes and the kernel's name; an
on-disk executable written under the old layout version is a miss."""
import collections
import glob
import os
import shutil
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import monitoring as mon
from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.generation.decode import BertDecoder
from deeplearning4j_tpu.generation.server import GenerationServer
from deeplearning4j_tpu.models.bert import BertConfig, init_bert_params
from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                   NeuralNetConfiguration, OutputLayer, Sgd)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.runtime import executables

PREFIX = mon.PROFILER_PREFIX
Event = collections.namedtuple("Event", "thread name start end stats")


@pytest.fixture(autouse=True)
def _monitoring_off_around():
    # before too: a worker that ran another file first may hold its events
    mon.disable()
    mon.get_tracer().clear()
    yield
    mon.disable()
    mon.get_tracer().clear()


class _Session:
    """A `jax.profiler` session as the benchmark's `--trace 1` takes one
    (host annotations on, the interpreter's own calls off); `events` and
    `modules` are read from its trace after the `with`."""

    def __init__(self, directory):
        self.directory = str(directory)
        self.events, self.modules = [], collections.Counter()

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        from jax.profiler import ProfileData
        path, = glob.glob(os.path.join(self.directory, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        thread = 0
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                thread += 1
                for e in line.events:
                    st = dict(e.stats)
                    if e.name.startswith(PREFIX):
                        self.events.append(Event(
                            thread, e.name[len(PREFIX):], e.start_ns,
                            e.start_ns + e.duration_ns, st))
                    if "hlo_module" in st:      # XLA:CPU's device events
                        self.modules[st["hlo_module"]] += 1
        return False

    def named(self, name):
        return [e for e in self.events if e.name == name]


# -- the span layer ---------------------------------------------------------
@pytest.mark.parametrize("enabled", [False, True])
def test_span_lands_in_the_profiler_trace_with_its_stats(tmp_path, enabled):
    if enabled:
        mon.enable()
    with _Session(tmp_path) as s:
        with mon.span("unit.outer", step=3, k=2) as sp:
            sp.set_metadata(tokens=5)
            with mon.span("unit.inner"):
                pass
        assert list(mon.traced_iter([1, 2], "unit.next")) == [1, 2]
    outer, = s.named("unit.outer")
    inner, = s.named("unit.inner")
    assert outer.stats == {"step": 3, "k": 2, "tokens": 5}
    assert outer.start <= inner.start and inner.end <= outer.end
    assert len(s.named("unit.next")) == 3        # two items and the end
    recorded = mon.get_tracer().events()
    if not enabled:
        assert recorded == []        # the Tracer is monitoring's, not on
    else:
        args = {e["name"]: e["args"] for e in recorded}
        assert args["unit.outer"]["tokens"] == 5
        assert args["unit.outer"]["step"] == 3
        assert args["unit.inner"]["depth"] == 1


def test_no_session_no_spans(tmp_path):
    with mon.span("unit.before", step=1):
        pass
    with _Session(tmp_path) as s:
        pass
    assert s.events == []
    assert mon.get_tracer().events() == []


# -- fit() --------------------------------------------------------------------
def _mlp():
    conf = (NeuralNetConfiguration.Builder()
            .seed(1).updater(Sgd(0.1)).activation("relu").list()
            .layer(DenseLayer.Builder().nOut(8).build())
            .layer(OutputLayer.Builder("mcxent").nOut(2)
                   .activation("softmax").build())
            .setInputType(InputType.feedForward(4)).build())
    return MultiLayerNetwork(conf).init()


def _graph():
    conf = (NeuralNetConfiguration.Builder()
            .seed(1).updater(Sgd(0.1)).activation("relu")
            .graphBuilder().addInputs("in")
            .addLayer("d", DenseLayer.Builder().nOut(8).build(), "in")
            .addLayer("out", OutputLayer.Builder("mcxent").nOut(2)
                      .activation("softmax").build(), "d")
            .setOutputs("out")
            .setInputTypes(InputType.feedForward(4)).build())
    return ComputationGraph(conf).init()


@pytest.mark.parametrize("build,module", [
    (_mlp, "jit_multilayer_train_step"), (_graph, "jit_graph_train_step")],
    ids=["multilayer", "graph"])
def test_fit_under_a_session_holds_the_host_spans(tmp_path, build, module):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 32)]
    net = build()
    net.fit(ArrayDataSetIterator(x, y, 8))          # compiled before
    steps = 4
    with _Session(tmp_path) as s:
        net.fit(ArrayDataSetIterator(x, y, 8))
        float(net.score())          # the steps ran before the trace ends
    for name in ("fit.epoch", "fit.data_next", "train.stage",
                 "train.dispatch", "train.listeners", "pipeline.stage",
                 "pipeline.wait"):
        assert s.named(name), name
    assert len(s.named("train.dispatch")) == steps
    # the prefetch worker stages on its own lane, with the bytes it staged
    main = s.named("train.dispatch")[0].thread
    staged = s.named("pipeline.stage")
    assert {e.thread for e in staged} != {main}
    assert all(e.stats["bytes"] == 8 * 4 * 4 + 8 * 2 * 4 for e in staged)
    assert all(e.thread == main for e in s.named("pipeline.wait"))
    # a step opens at most ten spans, on all threads together
    assert len(s.events) <= 10 * steps + 10
    assert s.modules[module] > 0
    assert not any(m in ("jit_step", "jit_run") for m in s.modules)
    assert mon.get_tracer().events() == []


# -- GenerationServer ---------------------------------------------------------
@pytest.fixture(scope="module")
def bert():
    cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=64)
    return cfg, init_bert_params(cfg, jax.random.PRNGKey(0))


def test_server_under_a_session_holds_spans_stats_and_names(tmp_path, bert):
    cfg, params = bert
    slots, new = 4, 6
    srv = GenerationServer(BertDecoder(cfg, params), slots=slots,
                           cache_lengths=[32], prompt_buckets=[8, 16],
                           seed=1)
    srv.warmup()
    try:
        with _Session(tmp_path / "on") as s:
            handles = [srv.submit(np.arange(1, 6 + i, dtype=np.int32),
                                  max_new_tokens=new, eos_id=None,
                                  method="greedy" if i % 2 else "sample")
                       for i in range(6)]
            for h in handles:
                assert len(h.result(timeout=120)) == new
        # and none of it when no session is on
        srv.generate([1, 2, 3], max_new_tokens=2, timeout=120)
        with _Session(tmp_path / "off") as quiet:
            pass
    finally:
        srv.shutdown()
    assert quiet.events == [] and mon.get_tracer().events() == []

    # spans of one request share `req`: submit on the client's thread,
    # admit and its fetch on the loop's
    submits = {e.stats["req"]: e for e in s.named("serve.submit")}
    admits = {e.stats["req"]: e for e in s.named("serve.admit")}
    assert sorted(submits) == sorted(admits) == [h.seq for h in handles]
    loop = s.named("serve.dispatch")[0].thread
    for req, a in admits.items():
        assert a.thread == loop != submits[req].thread
        assert a.stats["prompt_len"] == 4 + req
        assert a.stats["bucket"] == (8 if a.stats["prompt_len"] <= 8 else 16)
        # queue wait is reckoned from submit()'s stamp to the admit's start
        assert 0 <= a.stats["queue_wait_us"] \
            <= (a.start - submits[req].start) / 1e3 + 1
        fetch, = [f for f in s.named("serve.fetch")
                  if f.stats.get("req") == req]
        assert a.start <= fetch.start and fetch.end <= a.end
    assert max(a.stats["queue_wait_us"] for a in admits.values()) > 0

    # spans of one superstep share `step`
    dispatches = {e.stats["step"]: e for e in s.named("serve.dispatch")}
    delivers = {e.stats["step"]: e for e in s.named("serve.deliver")}
    assert len(dispatches) == len(s.named("serve.dispatch"))
    assert set(delivers) <= set(dispatches) and len(delivers) >= 2
    assert all(e.stats["k"] == 1 and 1 <= e.stats["active"] <= slots
               for e in dispatches.values())
    assert max(e.stats["active"] for e in dispatches.values()) == slots
    for step, d in delivers.items():
        fetch, = [f for f in s.named("serve.fetch")
                  if f.stats.get("step") == step]
        assert d.start <= fetch.start and fetch.end <= d.end
        assert d.start >= dispatches[step].end   # delivered a step later
    # every token but each request's first arrives in a deliver span
    assert sum(e.stats["tokens"] for e in delivers.values()) \
        == len(handles) * (new - 1)
    # a superstep opens at most ten spans (admissions bring their own)
    per_step = len(s.events) - 3 * len(admits) - len(s.named("serve.idle"))
    assert per_step <= 10 * len(dispatches)

    assert s.modules["jit_superstep"] and s.modules["jit_admit"]
    assert s.modules["jit_retire"]
    assert "jit_run" not in s.modules


def test_idle_server_waits_in_a_span(tmp_path, bert):
    cfg, params = bert
    srv = GenerationServer(BertDecoder(cfg, params), slots=2,
                           cache_lengths=[16], prompt_buckets=[8], seed=1)
    srv.warmup()
    try:
        with _Session(tmp_path) as s:
            srv.generate([1, 2], max_new_tokens=2, timeout=120)
            time.sleep(0.15)        # two of the loop's 50 ms waits
    finally:
        srv.shutdown()
    idle = s.named("serve.idle")
    assert idle and all(e.end - e.start <= 0.2e9 for e in idle)


# -- names on what the device runs --------------------------------------------
def test_lowered_decode_programs_hold_scopes_and_kernel_name(bert):
    cfg, params = bert
    dec = BertDecoder(cfg, params, attn_impl="pallas")   # interpreted here
    srv = GenerationServer(dec, slots=4, cache_lengths=[32],
                           prompt_buckets=[8])
    sds = jax.ShapeDtypeStruct
    margs = tuple(dec.model_args())
    spec = srv._state_spec(32)
    slot_i, scalar_i = sds((4,), jnp.int32), sds((), jnp.int32)
    step = jax.jit(srv._traced_superstep(1)).lower(
        *margs, *spec, slot_i, slot_i).as_text(debug_info=True)
    admit = jax.jit(srv._traced_admit).lower(
        *margs, *spec, scalar_i, sds((8,), jnp.int32), scalar_i,
        sds((2,), jnp.uint32), scalar_i, sds((), jnp.float32),
        scalar_i).as_text(debug_info=True)
    for text, kernel_scope in ((step, "layer1/attn/flash_decode/flash_fwd"),
                               (admit, "layer1/attn/flash_fwd")):
        for scope in ("embed/", "layer0/qkv/", "layer0/kv_write/",
                      "layer0/attn/", "layer0/proj/", "layer0/ffn/",
                      "layer1/qkv/", "logits/", "sample/",
                      "sample/select/", kernel_scope):
            assert scope in text, scope


def test_stores_name_their_programs(tmp_path):
    store = executables.FunctionStore("fp-names", directory=str(tmp_path))
    store.register("superstep", lambda x: x + 1)
    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    e = store.load_or_compile(("superstep", 4), (x,))
    assert "HloModule jit_superstep" in e.call.as_text()
    assert store.trace_calls == 1
    es = executables.ExecutableStore(_mlp(), directory=str(tmp_path))
    assert [f.__name__ for f in es._fwds.values()] \
        == ["forward", "forward_masked"]


def test_old_layout_entry_is_a_miss_and_load_time_is_summed(tmp_path,
                                                            monkeypatch):
    assert executables.LAYOUT_VERSION != "v2"
    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    key = ("double", 4)

    def store():
        s = executables.FunctionStore("fp-layout", directory=str(tmp_path))
        s.register("double", lambda a: a * 2)
        return s

    # an entry as the store before this layout wrote it
    monkeypatch.setattr(executables, "LAYOUT_VERSION", "v2")
    old = store()
    old.load_or_compile(key, (x,))
    old_path = old._entry_path(key)
    assert os.path.exists(old_path) and os.sep + "v2" + os.sep in old_path
    monkeypatch.undo()

    fresh = store()
    fresh.load_or_compile(key, (x,))
    assert fresh.stats["disk_hits"] == 0 and fresh.stats["compiles"] == 1
    assert fresh.stats["compile_seconds"] > 0
    assert fresh.stats["load_seconds"] == 0
    # even copied to where this layout looks, its meta gives it away
    new_path = fresh._entry_path(key)
    assert os.path.exists(new_path)
    shutil.copy(old_path, new_path)
    moved = store()
    moved.load_or_compile(key, (x,))
    assert moved.stats["disk_hits"] == 0
    assert moved.stats["deserialize_failures"] == 1
    assert moved.stats["compiles"] == 1

    # a restart on this layout's own entry loads it, and says how long
    warm = store()
    out = warm.load_or_compile(key, (x,)).call(jnp.ones((4,), jnp.float32))
    assert np.allclose(out, 2.0)
    assert warm.stats["disk_hits"] == 1 and warm.stats["compiles"] == 0
    assert warm.stats["load_seconds"] > 0
    assert warm.status()["load_seconds"] == warm.stats["load_seconds"]
    assert any(s.get("load_seconds") == warm.stats["load_seconds"]
               for s in executables.status()["stores"])


def test_miss_path_spans_carry_the_store_key(tmp_path):
    store = executables.FunctionStore("fp-spans",
                                      directory=str(tmp_path / "exec"))
    store.register("triple", lambda a: a * 3)
    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    with _Session(tmp_path / "trace") as s:
        store.load_or_compile(("triple", 4), (x,))
        again = executables.FunctionStore("fp-spans",
                                          directory=str(tmp_path / "exec"))
        again.register("triple", lambda a: a * 3)
        again.load_or_compile(("triple", 4), (x,))
    compiled, = s.named("exec.compile")
    loaded, = s.named("exec.load")
    assert compiled.stats["key"] == loaded.stats["key"] == "('triple', 4)"
