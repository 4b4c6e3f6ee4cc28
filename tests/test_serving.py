"""Serving-grade AOT executable cache + shape-bucketed continuous
batching (runtime/executables.py + parallel/inference.py).

The three acceptance properties of the serving layer:
- STEADY STATE: after warmup(), a stream of mixed-shape requests inside
  the ladder performs ZERO jit cache misses and ZERO live traces;
  oversized requests split across buckets instead of compiling a new
  shape.
- COLD START: a fresh ParallelInference pointed at a warm on-disk cache
  reaches its first response without invoking XLA compilation
  (executables deserialize; tier counters prove it); corrupt or
  mismatched entries fall back to a live compile, never crash.
- DONATION SAFETY: staged inputs are XLA-owned copies, never aliases of
  numpy memory (the PR 2 `xla_owned_copy` stress pattern), so the
  executables may donate their input buffers.
"""
import os
import pickle
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import monitoring as mon
from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                   NeuralNetConfiguration, OutputLayer, Sgd)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                   ParallelInference)
from deeplearning4j_tpu.runtime import executables as exe


@pytest.fixture(autouse=True)
def _monitoring_off_after():
    yield
    mon.disable()
    mon.get_tracer().clear()


def _conf():
    return (NeuralNetConfiguration.Builder()
            .seed(3).updater(Sgd(0.1)).activation("tanh")
            .list()
            .layer(DenseLayer.Builder().nOut(8).build())
            .layer(OutputLayer.Builder("mcxent").nOut(3)
                   .activation("softmax").build())
            .setInputType(InputType.feedForward(5))
            .build())


@pytest.fixture(scope="module")
def net():
    return MultiLayerNetwork(_conf()).init()


def _counter(name):
    return mon.get_registry().counter(name).value


# ===================== BucketLadder =====================
class TestBucketLadder:
    def test_bucket_routing(self):
        lad = exe.BucketLadder(batch=[1, 2, 4, 8])
        assert lad.bucket(1) == 1 and lad.bucket(3) == 4
        assert lad.bucket(8) == 8 and lad.bucket(9) is None
        assert lad.max_batch == 8

    def test_chunks_split_oversized(self):
        lad = exe.BucketLadder(batch=[2, 4, 8])
        assert lad.chunks(20) == [8, 8, 4]
        assert lad.chunks(8) == [8]
        assert lad.chunks(3) == [3]

    def test_length_buckets_never_truncate(self):
        lad = exe.BucketLadder(batch=[4], length=[4, 8])
        assert lad.length_bucket(3) == 4
        assert lad.length_bucket(8) == 8
        # over-long sequences serve at native length, never truncated
        assert lad.length_bucket(11) == 11

    def test_rejects_bad_buckets(self):
        with pytest.raises(ValueError):
            exe.BucketLadder(batch=[0, 2])
        with pytest.raises(ValueError):
            exe.BucketLadder(batch=[2], length=[0])


# ===================== steady state: zero compiles =====================
def test_steady_state_mixed_shapes_zero_misses_zero_traces(net):
    """ACCEPTANCE: post-warmup, mixed-shape traffic inside the ladder
    never touches jit — cache-miss counters and the store's python
    trace count both stay FLAT; oversized batches split."""
    pi = (ParallelInference.Builder(net)
          .inferenceMode(InferenceMode.BATCHED)
          .bucketLadder([1, 2, 4, 8]).build())
    try:
        stats = pi.warmup()
        assert stats["compiled"] + stats["from_disk"] == 4
        mon.enable()
        jit0 = _counter(mon.JIT_CACHE_MISSES)
        exe0 = _counter(mon.EXEC_COMPILES)
        traces = pi._store.trace_calls
        rng = np.random.default_rng(0)
        for n in (1, 2, 3, 5, 8, 7, 1, 20, 4, 6):   # 20 is oversized
            x = rng.standard_normal((n, 5)).astype(np.float32)
            np.testing.assert_allclose(pi.output(x),
                                       net.output(x).numpy(),
                                       atol=1e-5, rtol=1e-5)
        assert _counter(mon.JIT_CACHE_MISSES) - jit0 == 0
        assert _counter(mon.EXEC_COMPILES) - exe0 == 0
        assert pi._store.stats["compiles"] == 4     # warmup only
        assert pi._store.trace_calls == traces      # zero live traces
        # the oversized 20-row batch split 8+8+4, no new signature
        assert _counter(mon.SERVING_SPLITS) >= 1
        assert pi._aot_error is None
    finally:
        pi.shutdown()


def test_padding_waste_metrics(net):
    pi = (ParallelInference.Builder(net)
          .inferenceMode(InferenceMode.BATCHED)
          .bucketLadder([4]).build())
    try:
        pi.warmup()
        mon.enable()
        rows0 = _counter(mon.SERVING_ROWS)
        pad0 = _counter(mon.SERVING_PADDED_ROWS)
        occ = mon.get_registry().histogram(mon.SERVING_BUCKET_OCCUPANCY)
        occ0, osum0 = occ.count, occ.sum
        pi.output(np.zeros((3, 5), np.float32))     # pads 3 -> 4
        assert _counter(mon.SERVING_ROWS) - rows0 == 3
        assert _counter(mon.SERVING_PADDED_ROWS) - pad0 == 1
        assert occ.count - occ0 == 1
        assert abs((occ.sum - osum0) - 0.75) < 1e-9
    finally:
        pi.shutdown()


def test_concurrent_clients_exact_with_aot(net):
    """The PR 2/3-era concurrency contract holds on the AOT path:
    exact per-request answers, coalesced into few forwards."""
    pi = (ParallelInference.Builder(net)
          .inferenceMode(InferenceMode.BATCHED)
          .bucketLadder([1, 2, 4, 8, 16]).build())
    rng = np.random.default_rng(1)
    xs = rng.standard_normal((40, 5)).astype(np.float32)
    want = net.output(xs).numpy()
    got, errs = [None] * 40, []

    def client(i):
        try:
            got[i] = pi.output(xs[i])
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(40)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    pi.shutdown()
    assert not errs, errs
    for i in range(40):
        np.testing.assert_allclose(got[i], want[i], atol=1e-5, rtol=1e-5)
    assert pi.model_calls < 40
    assert pi._aot_error is None


# ===================== cold start from warm disk =====================
def test_cold_start_warm_disk_cache_compiles_nothing(tmp_path):
    """ACCEPTANCE: a fresh replica pointed at a warm cache dir reaches
    its first response by DESERIALIZING executables — the cache-tier
    counters prove XLA compilation never ran."""
    d = str(tmp_path / "exec")
    x = np.random.default_rng(2).standard_normal((3, 5)).astype(np.float32)

    net1 = MultiLayerNetwork(_conf()).init()
    pi1 = (ParallelInference.Builder(net1)
           .bucketLadder([2, 4]).executableCacheDir(d).build())
    warm = pi1.warmup()
    pi1.shutdown()
    assert warm["compiled"] == 2 and warm["from_disk"] == 0

    # "restarted replica": fresh model object, same architecture
    net2 = MultiLayerNetwork(_conf()).init()
    pi2 = (ParallelInference.Builder(net2)
           .bucketLadder([2, 4]).executableCacheDir(d).build())
    try:
        mon.enable()
        dh0 = _counter(mon.EXEC_DISK_HITS)
        stats = pi2.warmup()
        assert stats["compiled"] == 0
        assert stats["from_disk"] == 2
        assert _counter(mon.EXEC_DISK_HITS) - dh0 == 2
        np.testing.assert_allclose(pi2.output(x),
                                   net2.output(x).numpy(),
                                   atol=1e-5, rtol=1e-5)
        assert pi2._store.stats["compiles"] == 0    # never compiled
        assert pi2._store.trace_calls == 0          # never even traced
    finally:
        pi2.shutdown()


def test_corrupt_cache_entry_falls_back_to_live_compile(tmp_path):
    """ACCEPTANCE: garbage bytes / wrong-version entries are counted,
    removed, and recompiled — serving never crashes on a bad cache."""
    d = str(tmp_path / "exec")
    net1 = MultiLayerNetwork(_conf()).init()
    store1 = exe.ExecutableStore(net1, directory=d)
    sig = (((4, 5), "float32"),)
    store1.warmup([sig])
    path = store1._entry_path((sig, False))
    with open(path, "wb") as f:
        f.write(b"not an executable")

    store2 = exe.ExecutableStore(MultiLayerNetwork(_conf()).init(),
                                 directory=d)
    stats = store2.warmup([sig])
    assert store2.stats["deserialize_failures"] == 1
    assert stats["compiled"] == 1 and stats["from_disk"] == 0
    # the rewritten entry is valid again for the NEXT replica
    store3 = exe.ExecutableStore(MultiLayerNetwork(_conf()).init(),
                                 directory=d)
    assert store3.warmup([sig])["from_disk"] == 1


def test_meta_mismatch_treated_as_corrupt(tmp_path):
    """A cache written by a different jax/layout/flavour must MISS (and
    recompile), not deserialize foreign machine code."""
    d = str(tmp_path / "exec")
    net1 = MultiLayerNetwork(_conf()).init()
    store1 = exe.ExecutableStore(net1, directory=d)
    sig = (((2, 5), "float32"),)
    store1.warmup([sig])
    path = store1._entry_path((sig, False))
    with open(path, "rb") as f:
        rec = pickle.load(f)
    rec["meta"]["jax"] = "0.0.0-foreign"
    with open(path, "wb") as f:
        pickle.dump(rec, f)
    store2 = exe.ExecutableStore(MultiLayerNetwork(_conf()).init(),
                                 directory=d)
    assert store2.warmup([sig])["compiled"] == 1
    assert store2.stats["deserialize_failures"] == 1


def test_disk_entry_reloads_onto_its_own_devices_and_runs(tmp_path):
    """A one-device executable persisted by one store, loaded by a second
    store on the same directory under the 8-device test mesh, must load
    onto the device it was compiled for and RUN: jax's default loads it
    onto every local device, which only fails at the first call
    ('Expected args ... to have 8 shards') — past every load-time
    check."""
    assert len(jax.local_devices()) == 8
    d = str(tmp_path / "exec")
    x = jnp.arange(16, dtype=jnp.float32).reshape(4, 4)

    def build():
        return exe.FunctionStore("reload-devices", directory=d).register(
            "affine", lambda a: a * 2.0 + 1.0)

    first = build().load_or_compile(("affine", 4), (x,))
    assert first.source == "compile"
    store2 = build()
    e = store2.load_or_compile(("affine", 4), (x,))
    assert e.source == "disk" and store2.stats["compiles"] == 0
    out = e.call(x)                         # the call is the test
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2 + 1)
    assert out.devices() == {jax.local_devices()[0]}
    path = store2._entry_path(("affine", 4), exe._shape_dtype_tree((x,)))
    with open(path, "rb") as f:
        assert pickle.load(f)["devices"] == [jax.local_devices()[0].id]


def test_disk_entry_for_devices_this_host_lacks_is_a_miss(tmp_path):
    """An entry compiled for devices this host cannot supply is neither
    loaded nor counted corrupt: the store compiles live."""
    d = str(tmp_path / "exec")
    x = jnp.ones((4,), jnp.float32)

    def build():
        return exe.FunctionStore("foreign-devices", directory=d).register(
            "inc", lambda a: a + 1.0)

    store1 = build()
    store1.load_or_compile(("inc",), (x,))
    path = store1._entry_path(("inc",), exe._shape_dtype_tree((x,)))
    with open(path, "rb") as f:
        rec = pickle.load(f)
    rec["devices"] = [10 ** 6]
    with open(path, "wb") as f:
        pickle.dump(rec, f)
    store2 = build()
    e = store2.load_or_compile(("inc",), (x,))
    assert e.source == "compile"
    assert store2.stats["deserialize_failures"] == 0
    np.testing.assert_array_equal(np.asarray(e.call(x)), 2.0)


def test_different_architecture_different_fingerprint(tmp_path, net):
    other = (NeuralNetConfiguration.Builder().seed(3).updater(Sgd(0.1))
             .list()
             .layer(DenseLayer.Builder().nOut(16).build())
             .layer(OutputLayer.Builder("mcxent").nOut(3)
                    .activation("softmax").build())
             .setInputType(InputType.feedForward(5)).build())
    a = exe.model_fingerprint(net)
    b = exe.model_fingerprint(MultiLayerNetwork(other).init())
    assert a != b
    # same conf → same fingerprint (retrained replicas share a cache)
    assert a == exe.model_fingerprint(MultiLayerNetwork(_conf()).init())


# ===================== donation safety (PR 2 stress pattern) ==========
def test_staging_ring_never_aliases_host_memory():
    """The xla_owned_copy stress harness applied to StagingRing: every
    staged device buffer owns its memory — mutating (or freeing) the
    host array after stage() can never corrupt the dispatch."""
    ring = exe.StagingRing(depth=2)
    rng = np.random.default_rng(0)
    for _ in range(8):
        host = rng.standard_normal((16, 5)).astype(np.float32)
        keep = host.copy()
        (buf,) = ring.stage([host])
        host[...] = np.nan          # simulate the producer reusing it
        back = np.asarray(buf)
        assert not np.shares_memory(back, host)
        np.testing.assert_array_equal(back, keep)
        ring.release()


def test_staging_ring_bounds_depth():
    ring = exe.StagingRing(depth=1)
    assert ring.stage([np.zeros((2, 2), np.float32)]) is not None
    # full ring: non-blocking stage refuses instead of running ahead
    assert ring.stage([np.zeros((2, 2), np.float32)],
                      block=False) is None
    ring.release()
    assert ring.stage([np.zeros((2, 2), np.float32)],
                      block=False) is not None


def test_donating_dispatch_stress(net):
    """Serve a stream through the donated AOT path while mutating the
    request arrays afterwards — answers stay exact (no host-owned
    aliasing anywhere between request and executable)."""
    pi = (ParallelInference.Builder(net)
          .inferenceMode(InferenceMode.BATCHED)
          .bucketLadder([1, 2, 4]).build())
    try:
        pi.warmup()
        rng = np.random.default_rng(3)
        for _ in range(14):
            x = rng.standard_normal((3, 5)).astype(np.float32)
            want = net.output(x.copy()).numpy()
            got = pi.output(x)
            x[...] = np.nan         # caller reuses the buffer
            np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
        assert pi._aot_error is None
    finally:
        pi.shutdown()


# ===================== resilience of the AOT path =====================
def test_aot_failure_degrades_to_legacy_path(net):
    """A broken executable layer must never take serving down: the
    first failure opens the AOT breaker, requests keep answering on
    the legacy live path, and an explicit re-warm (the operator fixed
    the cause) closes the breaker and restores the AOT fast path —
    the fallback is a cooldown, never a lifetime revert."""
    from deeplearning4j_tpu.resilience.policy import CircuitBreaker
    pi = (ParallelInference.Builder(net)
          .inferenceMode(InferenceMode.BATCHED)
          .bucketLadder([2, 4]).build())
    try:
        pi.warmup()
        good_lookup = pi._store.lookup
        pi._store.lookup = None     # poison: TypeError on next dispatch
        mon.enable()
        fb0 = _counter(mon.SERVING_AOT_FALLBACKS)
        x = np.random.default_rng(4).standard_normal((2, 5)).astype(
            np.float32)
        np.testing.assert_allclose(pi.output(x), net.output(x).numpy(),
                                   atol=1e-5, rtol=1e-5)
        assert pi._aot_breaker.state == CircuitBreaker.OPEN
        assert pi._ladder is not None       # NOT permanently degraded
        assert pi._aot_error is not None
        assert _counter(mon.SERVING_AOT_FALLBACKS) - fb0 == 1
        # and stays up on the legacy path during the cooldown (one
        # fallback event — the open breaker sheds without re-trying)
        np.testing.assert_allclose(pi.output(x), net.output(x).numpy(),
                                   atol=1e-5, rtol=1e-5)
        assert _counter(mon.SERVING_AOT_FALLBACKS) - fb0 == 1
        # the operator fixes the cause and re-warms: the breaker
        # closes and the next dispatch is back on the AOT path
        pi._store.lookup = good_lookup
        pi.warmup()
        assert pi._aot_breaker.state == CircuitBreaker.CLOSED
        traces = pi._store.trace_calls
        np.testing.assert_allclose(pi.output(x), net.output(x).numpy(),
                                   atol=1e-5, rtol=1e-5)
        assert pi._store.trace_calls == traces    # zero-trace again
    finally:
        pi.shutdown()


# ===================== sequence length bucketing =====================
def test_length_bucketed_lstm_exact_and_compile_free():
    from deeplearning4j_tpu.nn.conf.recurrent import LSTM, RnnOutputLayer
    conf = (NeuralNetConfiguration.Builder().seed(5).updater(Sgd(0.1))
            .list()
            .layer(LSTM(nOut=6, activation="tanh"))
            .layer(RnnOutputLayer(nOut=3, activation="softmax",
                                  lossFunction="mcxent"))
            .setInputType(InputType.recurrent(4)).build())
    net = MultiLayerNetwork(conf).init()
    pi = (ParallelInference.Builder(net)
          .bucketLadder([1, 2]).lengthBuckets([4, 8]).build())
    try:
        stats = pi.warmup()
        assert stats["signatures"] == 4     # 2 batch x 2 length rungs
        compiles = pi._store.stats["compiles"]
        traces = pi._store.trace_calls
        rng = np.random.default_rng(0)
        for n, t in ((1, 3), (2, 4), (1, 8), (2, 6), (1, 1)):
            x = rng.standard_normal((n, t, 4)).astype(np.float32)
            got = pi.output(x)
            want = net.output(x).numpy()
            assert got.shape == want.shape
            np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
        assert pi._store.stats["compiles"] == compiles
        assert pi._store.trace_calls == traces
        assert pi._aot_error is None
    finally:
        pi.shutdown()


def test_length_tolerance_only_when_first_input_is_the_sequence(net):
    """Coalescing tolerance for differing time axes mirrors what
    _serve_aot can actually serve (mask + length bucket come from
    input 0): with a static first input, mismatched-T requests must
    become strays — never an un-concatenatable batch."""
    from deeplearning4j_tpu.parallel.inference import _Request
    pi = (ParallelInference.Builder(net)
          .inferenceMode(InferenceMode.SEQUENTIAL)
          .bucketLadder([2]).lengthBuckets([8]).build())
    f32 = np.float32
    static_first = [
        _Request((np.zeros((1, 4), f32), np.zeros((1, t, 3), f32)))
        for t in (5, 7)]
    assert pi._incompatible(static_first[1], static_first[0])
    seq_first = [
        _Request((np.zeros((1, t, 3), f32), np.zeros((1, 4), f32)))
        for t in (5, 7)]
    assert not pi._incompatible(seq_first[1], seq_first[0])


# ===================== multi-input graphs =====================
def test_multi_input_graph_aot(net):
    from deeplearning4j_tpu.nn.conf.graph_vertices import MergeVertex
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Sgd(0.1))
            .graphBuilder()
            .addInputs("a", "b")
            .addLayer("da", DenseLayer(nOut=6, activation="tanh"), "a")
            .addLayer("db", DenseLayer(nOut=6, activation="tanh"), "b")
            .addVertex("merge", MergeVertex(), "da", "db")
            .addLayer("out", OutputLayer(nOut=3, activation="softmax"),
                      "merge")
            .setOutputs("out")
            .setInputTypes(InputType.feedForward(4),
                           InputType.feedForward(5))
            .build())
    g = ComputationGraph(conf).init()
    pi = ParallelInference.Builder(g).bucketLadder([1, 2, 4]).build()
    try:
        stats = pi.warmup()     # shapes derived from both InputTypes
        assert stats["signatures"] == 3
        traces = pi._store.trace_calls
        rng = np.random.default_rng(2)
        a = rng.standard_normal((3, 4)).astype(np.float32)
        b = rng.standard_normal((3, 5)).astype(np.float32)
        want = np.asarray(g.output([a, b]).numpy())
        np.testing.assert_allclose(pi.output([a, b]), want,
                                   atol=1e-5, rtol=1e-5)
        assert pi._store.trace_calls == traces
        assert pi._aot_error is None
    finally:
        pi.shutdown()


# ===================== persistent compile cache tiers =================
def test_persistent_cache_tier_counters():
    """dl4j.jit.persistent_{hits,misses} split every XLA compile into
    first-tier (live) vs persistent-tier (cross-process warm): the same
    program recompiled after clear_caches() must HIT."""
    exe.configure_persistent_cache()    # conftest set the dir already
    assert jax.config.jax_compilation_cache_dir

    def fn(x):
        return x * 3.0 + 1.5

    mon.enable()
    before = exe.persistent_cache_stats()
    jit0 = _counter(mon.JIT_PERSISTENT_HITS)
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        jax.jit(fn)(jnp.zeros((5,)))    # miss or hit: warms the cache
        jax.clear_caches()              # drop tier 0 (in-process)
        jax.jit(fn)(jnp.zeros((5,)))    # must come from the disk tier
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
    after = exe.persistent_cache_stats()
    assert after["hits"] > before["hits"]
    assert _counter(mon.JIT_PERSISTENT_HITS) > jit0


def test_store_never_persists_a_cache_served_cpu_executable(tmp_path,
                                                           monkeypatch):
    """XLA:CPU re-serializes an executable it was SERVED from jax's
    persistent cache into an incomplete payload that can load and then
    fail when run; the store must recompile such an entry outside the
    cache before persisting it — and the reloaded entry must run."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    # a private cache dir: sub-2 s entries must never reach the shared one
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    uncached = []
    real = exe._AotStoreBase._compile_uncached
    monkeypatch.setattr(
        exe._AotStoreBase, "_compile_uncached",
        staticmethod(lambda fn: uncached.append(1) or real(fn)))
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)

    def build(d):
        return exe.FunctionStore("pcache-served", directory=str(
            tmp_path / d)).register("f", lambda a: (a.T @ a) * 0.5)
    try:
        build("a").load_or_compile(("f",), (x,))    # writes the jax cache
        assert uncached == []
        jax.clear_caches()                          # next compile: a HIT
        build("b").load_or_compile(("f",), (x,))
        assert uncached == [1]
        e = build("b").load_or_compile(("f",), (x,))
        assert e.source == "disk"
        np.testing.assert_allclose(np.asarray(e.call(x)),
                                   np.asarray(x).T @ np.asarray(x) * 0.5,
                                   rtol=1e-6)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
        cc.reset_cache()


_CACHE_RULE_CHILD = """
import json, jax
from deeplearning4j_tpu.util.hostkey import enable_compile_cache
d = enable_compile_cache(min_compile_secs=0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
if {compile}:
    jax.jit(lambda x: x - 2.0)(jax.numpy.zeros((3,)))
print(json.dumps([d, jax.config.jax_compilation_cache_dir]))
"""


@pytest.mark.parametrize("env_set", [True, False],
                         ids=["JAX_COMPILATION_CACHE_DIR-set", "unset"])
def test_compile_cache_placement_rule(tmp_path, env_set):
    """The one compile-cache rule (util/hostkey.enable_compile_cache), in
    a fresh interpreter: with JAX_COMPILATION_CACHE_DIR set no directory
    is set in code and the entries land THERE; without it the cache is
    the checkout's fixed .jax_cache/host-<key>, derived from the
    package's location, never a temporary name."""
    import json
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    d = str(tmp_path / "cc")
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = d
    # the unset arm does not compile: a sub-2 s entry must never land in
    # the shared checkout cache (conftest's poison note)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_RULE_CHILD.format(compile=env_set)],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    returned, configured = json.loads(out.stdout.strip().splitlines()[-1])
    assert returned == configured
    if env_set:
        assert configured == d
        assert os.listdir(d)            # entries landed where jax was told
    else:
        from deeplearning4j_tpu.util.hostkey import host_cpu_key
        assert configured == os.path.join(repo, ".jax_cache",
                                          f"host-{host_cpu_key()}")


# ===================== status endpoint =====================
def test_executables_status_endpoint(net):
    import json
    import urllib.request

    from deeplearning4j_tpu.ui.server import UIServer
    pi = ParallelInference.Builder(net).bucketLadder([2]).build()
    server = UIServer.getInstance()
    server.start(port=0)
    try:
        pi.warmup()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/executables") as r:
            snap = json.loads(r.read())
        stores = [s for s in snap["stores"]
                  if s["fingerprint"] == pi._store.fingerprint]
        assert stores and stores[0]["entries"]
        assert stores[0]["compiles"] + stores[0]["disk_hits"] >= 1
        assert "persistent_compile_cache" in snap
    finally:
        pi.shutdown()
        server.stop()


# -- cold-start microbench (committed check; excluded from tier-1) ------
@pytest.mark.slow
def test_bench_serving_cold_vs_warm():
    import bench_serving
    result = bench_serving.run(requests=40)
    # disk-warm replica must beat the compiling one decisively (the
    # CPU-sized model measures ~9x; the 5x bar leaves load headroom)
    assert result["cold_vs_warm_speedup"] >= 5.0, result
    assert 0.0 <= result["padding_waste_ratio"] < 1.0


# ===================== fast-path lint: serving rules ==================
def test_serving_lint_flags_trace_on_dispatch_path():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    import check_fastpath
    bad = {"mod.py": (
        "import jax\n"
        "def _run(self, batch):\n"
        "    return self._go(batch)\n"
        "def _go(self, batch):\n"
        "    return jax.jit(lambda x: x)(batch)\n")}
    v = check_fastpath.check_serving_steady_state(bad)
    assert len(v) == 1 and "reachable from the serving dispatch" in v[0][2]
    # the declared miss boundary is allowed to compile
    ok = {"mod.py": (
        "import jax\n"
        "def _run(self, batch):\n"
        "    e = self.lookup(batch)\n"
        "    if e is None:\n"
        "        e = self.load_or_compile(batch)\n"
        "    return e\n"
        "def lookup(self, b):\n"
        "    return None\n"
        "def load_or_compile(self, b):\n"
        "    return jax.jit(lambda x: x)\n")}
    assert check_fastpath.check_serving_steady_state(ok) == []
