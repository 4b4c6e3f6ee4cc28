"""Quantization + selective-recompute subsystem (quantize/):

Tier-1 acceptance anchors (ISSUE 11):
- int8 inference agrees with the fp reference on a zoo model (top-1)
  and on pointwise-residual graphs (both the per-layer int8-dot impl
  and the cache-resident chain executor);
- QAT fake-quant trains with finite gradients through the STE;
- remat ("blocks" / "layers") gradients equal the un-rematted step and
  the traffic ledger reports >= 30% fewer saved-for-backward bytes;
- int8 KV-cache decode matches fp decode within tolerance (logits and
  greedy token stream).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph_vertices import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.layers import (ActivationLayer,
                                               BatchNormalization,
                                               ConvolutionLayer,
                                               DenseLayer,
                                               GlobalPoolingLayer,
                                               OutputLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.quantize import (PrecisionPolicy, fake_quant,
                                         per_channel_scales,
                                         quantize_network)
from deeplearning4j_tpu.quantize.core import INT8_MAX, dequantize, quantize
from deeplearning4j_tpu.quantize.traffic import activation_report


# ===================== shared fixtures ================================
def _residual_graph(remat="none", wide=12, narrow=6, blocks=2, hw=6,
                    seed=7):
    b = (NeuralNetConfiguration.Builder().seed(seed).updater(Sgd(0.05))
         .weightInit("relu").graphBuilder()
         .addInputs("input")
         .setInputTypes(InputType.convolutional(hw, hw, wide)))
    if remat != "none":
        b.rematPolicy(remat)
    x = "input"
    for i in range(blocks):
        b.addLayer(f"r{i}_c1", ConvolutionLayer(
            kernelSize=(1, 1), nOut=narrow, convolutionMode="same",
            hasBias=False, activation="identity"), x)
        b.addLayer(f"r{i}_bn1", BatchNormalization(activation="relu"),
                   f"r{i}_c1")
        b.addLayer(f"r{i}_c2", ConvolutionLayer(
            kernelSize=(1, 1), nOut=wide, convolutionMode="same",
            hasBias=False, activation="identity"), f"r{i}_bn1")
        b.addLayer(f"r{i}_bn2",
                   BatchNormalization(activation="identity"), f"r{i}_c2")
        b.addVertex(f"r{i}_add", ElementWiseVertex("add"),
                    f"r{i}_bn2", x)
        b.addLayer(f"r{i}_relu", ActivationLayer(activation="relu"),
                   f"r{i}_add")
        x = f"r{i}_relu"
    b.addLayer("pool", GlobalPoolingLayer(poolingType="avg"), x)
    b.addLayer("out", OutputLayer(lossFunction="mcxent", nOut=4,
                                  activation="softmax"), "pool")
    b.setOutputs("out")
    return ComputationGraph(b.build()).init()


@pytest.fixture(scope="module")
def trained_graph():
    net = _residual_graph()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 6, 6, 12)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 8)]
    for _ in range(5):
        net.fit(DataSet(x, y))
    return net, x


# ===================== core primitives ================================
def test_quantize_round_trip_per_channel():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((16, 8)) * 3, jnp.float32)
    s = per_channel_scales(w, -1)
    assert s.shape == (8,)
    q = quantize(w, s, channel_axis=1)
    assert q.dtype == jnp.int8
    back = dequantize(q, s, channel_axis=1)
    # round-trip error bounded by half a quantization step per channel
    assert float(jnp.max(jnp.abs(back - w) / s[None, :])) <= 0.5 + 1e-6


def test_fake_quant_ste_gradients():
    x = jnp.asarray([-300.0, -1.0, 0.3, 0.5, 1.0, 300.0], jnp.float32)
    s = jnp.asarray(1.0 / INT8_MAX, jnp.float32)
    g = jax.grad(lambda v: jnp.sum(fake_quant(v, s)))(x)
    # straight-through inside the clip range, zero outside
    np.testing.assert_array_equal(np.asarray(g),
                                  [0.0, 1.0, 1.0, 1.0, 1.0, 0.0])
    assert np.all(np.isfinite(np.asarray(g)))


def test_qat_training_gradients_finite():
    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Sgd(0.1))
            .precisionPolicy(PrecisionPolicy.int8())
            .list()
            .layer(DenseLayer(nOut=16, activation="relu"))
            .layer(OutputLayer(lossFunction="mcxent", nOut=3,
                               activation="softmax"))
            .setInputType(InputType.feedForward(8)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(2)
    x = rng.standard_normal((16, 8)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    s0 = None
    for _ in range(5):
        net.fit(x, y)
        s = net.score()
        assert np.isfinite(s)
        s0 = s if s0 is None else s0
    g = net.computeGradients(x, y)
    for leaf in jax.tree_util.tree_leaves(g):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    assert net.score() < s0   # STE gradients actually descend


# ===================== int8 inference =================================
def test_int8_zoo_model_top1_agreement():
    from deeplearning4j_tpu.models.zoo import LeNet
    net = LeNet(numClasses=10, inputShape=(14, 14, 1)).init()
    rng = np.random.default_rng(3)
    x = rng.standard_normal((32, 14, 14, 1)).astype(np.float32)
    q = quantize_network(net, data=[x])
    # LeNet: the 5x5 convs fall back to fp (counted), dense quantizes
    assert q._quant_stats["int8_layers"] >= 1
    assert q._quant_stats["fallbacks"] >= 2
    fp = net.output(x).numpy()
    qo = q.output(x).numpy()
    agree = float((fp.argmax(-1) == qo.argmax(-1)).mean())
    assert agree >= 0.95
    assert np.max(np.abs(fp - qo)) < 0.05


def test_int8_graph_chain_and_dot_agree(trained_graph):
    net, x = trained_graph
    fp = net.outputSingle(x).numpy()
    q_chain = quantize_network(net, data=[x], impl="chain")
    q_dot = quantize_network(net, data=[x], impl="dot")
    assert q_chain._quant_stats["chains"] >= 1
    assert q_chain._quant_stats["folded_bns"] == 4
    oc = q_chain.outputSingle(x).numpy()
    od = q_dot.outputSingle(x).numpy()
    assert float((fp.argmax(-1) == oc.argmax(-1)).mean()) == 1.0
    assert float((fp.argmax(-1) == od.argmax(-1)).mean()) == 1.0
    # both impls are int8-faithful; chain rounds less (cache-resident)
    assert np.max(np.abs(fp - oc)) < 0.05
    assert np.max(np.abs(fp - od)) < 0.05


def test_int8_bn_scale_calibration_without_data(trained_graph):
    net, x = trained_graph
    # no calibration data: conv2 nodes (fed by BN) derive scales from
    # the BN's gamma/beta; the rest fall back to the default
    q = quantize_network(net)
    srcs = {k: v[1] for k, v in q._quant_stats["scales"].items()}
    assert srcs["r0_c2"] == "bn-stats"
    assert srcs["r0_c1"] == "default"
    out = q.outputSingle(x).numpy()
    fp = net.outputSingle(x).numpy()
    assert float((fp.argmax(-1) == out.argmax(-1)).mean()) >= 0.75


def test_quantized_net_is_inference_only(trained_graph):
    net, x = trained_graph
    q = quantize_network(net, data=[x])
    with pytest.raises(RuntimeError, match="inference-only"):
        q.fit(None)


def test_quantize_policy_opt_out():
    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(nOut=8, nIn=4, activation="relu"))
            .layer(OutputLayer(lossFunction="mcxent", nOut=3,
                               activation="softmax"))
            .setInputType(InputType.feedForward(4)).build())
    net = MultiLayerNetwork(conf).init()
    pol = PrecisionPolicy.int8(min_channels=100)   # nothing qualifies
    with pytest.raises(ValueError, match="nothing to quantize"):
        quantize_network(net, policy=pol)


def test_per_layer_precision_policy_opt_out():
    """`.precisionPolicy(None)` on a layer builder must really opt the
    layer out — of QAT fake-quant AND the int8 rewrite — despite None
    being the inherit sentinel for every other field."""
    from deeplearning4j_tpu.quantize.infer import QuantizedDense
    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Sgd(0.1))
            .precisionPolicy(PrecisionPolicy.int8())
            .list()
            .layer(DenseLayer.Builder().nOut(16).activation("relu")
                   .precisionPolicy(None).build())
            .layer(DenseLayer(nOut=16, activation="relu"))
            .layer(OutputLayer(lossFunction="mcxent", nOut=3,
                               activation="softmax"))
            .setInputType(InputType.feedForward(8)).build())
    assert conf.layers[0].precisionPolicy.enabled is False
    assert conf.layers[0].precisionPolicy.applies_to(
        conf.layers[0]) is False
    assert conf.layers[1].precisionPolicy.enabled is True
    net = MultiLayerNetwork(conf).init()
    x = np.random.default_rng(0).standard_normal((8, 8)).astype(
        np.float32)
    q = quantize_network(net, data=[x])
    assert not isinstance(q.layers[0], QuantizedDense)   # opted out
    assert isinstance(q.layers[1], QuantizedDense)
    assert q._quant_stats["fallbacks"] == 1


def test_quantized_metrics_counted(trained_graph):
    net, x = trained_graph
    monitoring.enable()
    try:
        reg = monitoring.get_registry()
        before = reg.get(monitoring.QUANT_INT8_LAYERS)
        base = before.value if before is not None else 0
        quantize_network(net, data=[x])
        c = reg.get(monitoring.QUANT_INT8_LAYERS)
        assert c is not None and c.value >= base + 4
        assert reg.get(monitoring.QUANT_CALIBRATIONS) is not None
    finally:
        monitoring.disable()


def test_quantized_serving_executable_store(trained_graph, tmp_path):
    """Serving compiles quantized executables: the store fingerprints
    the int8 twin separately, steady state resolves from the memory
    tier (zero further traces), and the AOT output matches eager."""
    from deeplearning4j_tpu.runtime.executables import (ExecutableStore,
                                                        model_fingerprint)
    net, x = trained_graph
    q = quantize_network(net, data=[x])
    assert model_fingerprint(q) != model_fingerprint(net)
    store = ExecutableStore(q, directory=str(tmp_path))
    sig = ((tuple(np.shape(x)), "float32"),)
    e = store.load_or_compile(sig)
    out = np.asarray(e.call(q._params, q._state, jnp.asarray(x))[0])
    ref = q.outputSingle(x).numpy()
    np.testing.assert_allclose(out, ref, atol=1e-6)
    traces = store.trace_calls
    for _ in range(3):
        hit = store.lookup(sig)
        assert hit is not None
        hit.call(q._params, q._state, jnp.asarray(x))
    assert store.trace_calls == traces   # zero traces past warmup


# ===================== epilogue kernels ===============================
def test_matmul_epilogue_fused_matches_composition():
    from deeplearning4j_tpu.kernels import (int8_matmul_epilogue,
                                            matmul_epilogue)
    rng = np.random.default_rng(4)
    m, k, n = 70, 12, 9
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.3, jnp.float32)
    s = jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32)
    b = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)
    res = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    ref = np.maximum((np.asarray(x) @ np.asarray(w)) * np.asarray(s)
                     + np.asarray(b) + np.asarray(res), 0)
    out = matmul_epilogue(x, w, s, b, residual=res, act="relu",
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)

    xq = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    acc = np.asarray(xq, np.int32) @ np.asarray(wq, np.int32)
    ref8 = acc * np.asarray(s) * 1e-3 + np.asarray(b)
    out8 = int8_matmul_epilogue(xq, wq, s * 1e-3, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out8), ref8, rtol=1e-5,
                               atol=1e-4)


def test_fused_conv_bn_eval_epilogue():
    """fused.py's eval branch now folds BN+relu into the GEMM epilogue
    kernel — must equal the conv.apply→bn.apply composition."""
    from deeplearning4j_tpu.nn.fused import fused_apply
    rng = np.random.default_rng(5)
    conv = ConvolutionLayer(kernelSize=(1, 1), nIn=6, nOut=10,
                            hasBias=False, convolutionMode="same",
                            activation="identity")
    bn = BatchNormalization(nOut=10, activation="relu")
    bn.apply_defaults({})
    x = jnp.asarray(rng.standard_normal((2, 4, 4, 6)), jnp.float32)
    pc = {"W": jnp.asarray(rng.standard_normal((1, 1, 6, 10)) * 0.4,
                           jnp.float32)}
    pb = {"gamma": jnp.asarray(rng.uniform(0.5, 1.5, 10), jnp.float32),
          "beta": jnp.asarray(rng.standard_normal(10) * 0.1,
                              jnp.float32)}
    sb = {"mean": jnp.asarray(rng.standard_normal(10) * 0.05,
                              jnp.float32),
          "var": jnp.asarray(rng.uniform(0.5, 1.5, 10), jnp.float32)}
    z, ns, y = fused_apply(conv, bn, pc, pb, sb, x, train=False,
                           interpret=True)
    yc = conv.apply(pc, {}, x, train=False)[0]
    zr = bn.apply(pb, sb, yc, train=False)[0]
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yc), atol=1e-5)

    # autodiff THROUGH the traced eval path (input saliency etc.):
    # the epilogue kernel carries a custom VJP — gradients must match
    # the unfused composition for every differentiable input
    def fused_sum(xi, w, gamma, beta):
        zz, _, _ = fused_apply(conv, bn, {"W": w},
                               {"gamma": gamma, "beta": beta}, sb, xi,
                               train=False, interpret=True)
        return jnp.sum(zz * jnp.cos(zz))

    def unfused_sum(xi, w, gamma, beta):
        yy = conv.apply({"W": w}, {}, xi, train=False)[0]
        zz = bn.apply({"gamma": gamma, "beta": beta}, sb, yy,
                      train=False)[0]
        return jnp.sum(zz * jnp.cos(zz))

    gf = jax.jit(jax.grad(fused_sum, argnums=(0, 1, 2, 3)))(
        x, pc["W"], pb["gamma"], pb["beta"])
    gu = jax.jit(jax.grad(unfused_sum, argnums=(0, 1, 2, 3)))(
        x, pc["W"], pb["gamma"], pb["beta"])
    for a, b in zip(gf, gu):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)


# ===================== selective recompute ============================
@pytest.mark.slow   # suite diet: ~13 s (grad-compiles BOTH the plain
# and rematted graph); remat stays tier-1 via the training-step and
# layers-policy tests below — this is the bit-equality oracle only
def test_remat_blocks_gradients_equal():
    plain = _residual_graph("none")
    remat = _residual_graph("blocks")
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((4, 6, 6, 12)), jnp.float32)
    y = jnp.asarray(np.eye(4, dtype=np.float32)[
        rng.integers(0, 4, 4)])
    ins, labels = {"input": x}, [y]
    key = jax.random.PRNGKey(3)

    def grads(net):
        g, _ = jax.grad(lambda p: net._loss(p, net._state, ins, labels,
                                            None, None, key),
                        has_aux=True)(net._params)
        return g

    gp, gr = grads(plain), grads(remat)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)
    lp, _ = plain._loss(plain._params, plain._state, ins, labels, None,
                        None, key)
    lr, _ = remat._loss(remat._params, remat._state, ins, labels, None,
                        None, key)
    assert float(lp) == pytest.approx(float(lr), abs=1e-6)


def test_remat_blocks_training_step_runs():
    net = _residual_graph("blocks")
    rng = np.random.default_rng(7)
    x = rng.standard_normal((4, 6, 6, 12)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 4)]
    net.fit(DataSet(x, y))
    assert np.isfinite(net.score())


def test_remat_layers_policy_multilayer():
    def build(remat):
        b = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
             .list()
             .layer(DenseLayer(nOut=16, activation="tanh"))
             .layer(DenseLayer(nOut=16, activation="tanh"))
             .layer(OutputLayer(lossFunction="mcxent", nOut=3,
                                activation="softmax"))
             .setInputType(InputType.feedForward(8)))
        if remat:
            b.rematPolicy("layers")
        return MultiLayerNetwork(b.build()).init()

    plain, remat = build(False), build(True)
    assert remat.conf.layers[0].remat is True
    assert getattr(plain.conf.layers[0], "remat", None) is None
    rng = np.random.default_rng(8)
    x = rng.standard_normal((8, 8)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
    gp = plain.computeGradients(x, y)
    gr = remat.computeGradients(x, y)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)


def test_traffic_ledger_remat_reduction_and_gauge():
    plain = _residual_graph("none", wide=16, narrow=8, blocks=3, hw=8)
    remat = _residual_graph("blocks", wide=16, narrow=8, blocks=3, hw=8)
    rp = activation_report(plain, batch=4)
    rr = activation_report(remat, batch=4)
    assert rp["saved_bytes"] == rp["forward_bytes"]
    reduction = 1 - rr["saved_bytes"] / rp["saved_bytes"]
    assert reduction >= 0.30   # ISSUE acceptance bar
    monitoring.enable()
    try:
        from deeplearning4j_tpu.quantize.traffic import publish
        publish(remat, batch=4, model_name="resblock")
        text = monitoring.get_registry().prometheus_text()
        assert "dl4j_quant_activation_traffic_bytes" in text
    finally:
        monitoring.disable()


# ===================== int8 KV-cache decode ===========================
@pytest.fixture(scope="module")
def tiny_bert():
    from deeplearning4j_tpu.models.bert import bert_tiny, init_bert_params
    cfg = bert_tiny()
    params = init_bert_params(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _decode_stream(dec, prompt, steps=6):
    margs = dec.model_args()
    plen = len(prompt)
    cache = dec.init_cache(2, 32)
    cache, logits = dec.prefill(
        margs, cache, jnp.int32(1),
        jnp.asarray(np.pad(prompt, (0, 16 - plen))), jnp.int32(plen))
    toks, lgs = [int(jnp.argmax(logits))], [np.asarray(logits)]
    for t in range(steps):
        tv = jnp.zeros((2,), jnp.int32).at[1].set(toks[-1])
        pos = jnp.zeros((2,), jnp.int32).at[1].set(plen + t)
        lg, cache = dec.step(margs, cache, tv, pos)
        lgs.append(np.asarray(lg[1]))
        toks.append(int(jnp.argmax(lg[1])))
    return toks, lgs


def test_int8_kv_cache_decode_matches_fp(tiny_bert):
    from deeplearning4j_tpu.generation import BertDecoder
    cfg, params = tiny_bert
    prompt = np.random.default_rng(9).integers(
        1, cfg.vocab_size, 7).astype(np.int32)
    fp_toks, fp_lgs = _decode_stream(BertDecoder(cfg, params), prompt)
    q_dec = BertDecoder(cfg, params, kv_dtype="int8")
    q_toks, q_lgs = _decode_stream(q_dec, prompt)
    assert q_toks == fp_toks          # greedy stream identical
    for a, b in zip(fp_lgs, q_lgs):
        np.testing.assert_allclose(a, b, atol=2e-3)
    # cache really is int8 + per-(position, head) scales, a leaf a layer
    cache = q_dec.init_cache(2, 16)
    assert len(cache["k"]) == len(cache["ks"]) == cfg.num_layers
    assert cache["k"][0].dtype == jnp.int8
    assert cache["k"][0].shape == (2, 16, cfg.hidden_size)
    assert cache["ks"][0].shape == (2, 16, cfg.num_heads)
    # fingerprints differ: quantized executables cache separately
    assert (BertDecoder(cfg, params).fingerprint()
            != q_dec.fingerprint())


def test_int8_kv_cache_grow_pads_scales(tiny_bert):
    from deeplearning4j_tpu.generation import BertDecoder
    cfg, params = tiny_bert
    dec = BertDecoder(cfg, params, kv_dtype="int8")
    cache = dec.init_cache(2, 8)
    grown = dec.grow(cache, 16)
    for name in ("k", "v", "ks", "vs"):
        assert all(t.shape[:2] == (2, 16) for t in grown[name])
    # padded scale rows are 1.0 (zero rows round-trip exactly)
    assert all(float(jnp.min(t[:, 8:])) == 1.0 for t in grown["ks"])


def test_flash_decode_quantized_matches_dequantized_reference():
    from deeplearning4j_tpu.kernels.flash_attention import \
        flash_attention_decode
    from deeplearning4j_tpu.quantize.kvcache import (dequantize_rows,
                                                     quantize_rows)
    rng = np.random.default_rng(10)
    b, h, c, d = 3, 2, 11, 8
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, c, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, c, h, d)), jnp.float32)
    lens = np.array([0, 4, 11])   # incl. an empty-mask row
    mask = jnp.asarray(
        (np.arange(c)[None, :] < lens[:, None]).astype(np.float32))
    # per-(row, head) scales (B, C, H) beside (B, C, H·D) int8 rows

    def flat(t):
        return t.reshape(b, c, h * d)

    kq, ks = quantize_rows(k)
    vq, vs = quantize_rows(v)
    fused = flash_attention_decode(q, flat(kq), flat(vq), mask,
                                   k_scale=ks, v_scale=vs)
    # oracle: dequantize the cache, run the stock dense reference
    ref = flash_attention_decode(q, flat(dequantize_rows(kq, ks)),
                                 flat(dequantize_rows(vq, vs)), mask,
                                 impl="dense")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.asarray(fused[0]) == 0)   # empty row zeroed
    with pytest.raises(ValueError, match="k_scale and v_scale"):
        flash_attention_decode(q, flat(kq), flat(vq), mask, k_scale=ks)
    with pytest.raises(ValueError, match="must be given together"):
        flash_attention_decode(q, flat(kq), flat(vq), mask, v_scale=vs)
    with pytest.raises(ValueError, match=r"\(B, C, H\)"):
        flash_attention_decode(q, flat(kq), flat(vq), mask,
                               k_scale=ks.transpose(0, 2, 1),
                               v_scale=vs.transpose(0, 2, 1))


def test_int8_generation_server_stream(tiny_bert):
    """End to end through the GenerationServer: int8-cache decode
    serves the same greedy stream the fp-cache server does."""
    from deeplearning4j_tpu.generation import (BertDecoder,
                                               GenerationServer)
    cfg, params = tiny_bert
    prompt = list(np.random.default_rng(11).integers(
        1, cfg.vocab_size, 5))

    def serve(kv_dtype):
        srv = GenerationServer(
            BertDecoder(cfg, params, kv_dtype=kv_dtype), slots=2,
            cache_lengths=[32], prompt_buckets=[8], method="greedy",
            max_new_tokens=5, seed=0)
        try:
            srv.warmup()
            return srv.generate(prompt, timeout=60)
        finally:
            srv.shutdown()

    assert serve("int8") == serve("fp")
