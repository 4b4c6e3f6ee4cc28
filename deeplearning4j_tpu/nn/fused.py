"""Conv1x1 + BatchNorm fusion pass for ComputationGraph.

The reference reaches fused conv+BN through cuDNN helper classes
(deeplearning4j-cuda :: CudnnConvolutionHelper /
CudnnBatchNormalizationHelper chosen per-layer at runtime). The TPU-native
equivalent is a graph-level rewrite: a 1x1 convolution feeding only a
BatchNormalization is executed as ONE fused Pallas op
(kernels/pointwise_conv.fused_conv1x1_bn) — the conv becomes a GEMM with a
BN-stats epilogue, and BN's closed-form backward is reconstructed inside
the conv-gradient GEMMs instead of materializing the intermediate
gradient (see kernels/pointwise_conv.py for the pass accounting).

The rewrite is *execution-only*: node names, parameter trees, state
trees, serialization, transfer learning and constraints are all
unchanged — `mark_conv1x1_bn_fusions` just annotates node pairs, and the
graph executor routes the pair through `fused_apply` at train time.

OFF by default (opt in with DL4J_TPU_FUSE_CONV_BN=1): measured on the
v5e ResNet-50 headline bench the fused step was SLOWER (179 ms vs 99 ms,
round 3, 2026-07, other JAX — a negative result) — Pallas custom-calls are fusion barriers,
so the BN-apply/relu passes XLA used to merge with neighbours become
standalone, and the row-major GEMM operands force relayout copies
against XLA's batch-minor conv layouts. The kernels stay correct,
tested, and available for graphs where XLA's fusion does worse.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _eval_epilogue(xf, w, a, b, act, interpret):
    """act((xf @ w)·a + b) through the epilogue-fused Pallas kernel
    (kernels/pointwise_conv.matmul_epilogue), with a closed-form VJP:
    the kernel itself has no differentiation rule, but eval-mode
    forwards still get differentiated (input saliency, adversarial
    probes), so the backward recomputes the pre-affine GEMM and emits
    the standard affine/relu chain — grads to gamma/beta flow through
    the fold arithmetic outside this function."""
    from deeplearning4j_tpu.kernels.pointwise_conv import matmul_epilogue
    return matmul_epilogue(xf, w, a, b, act=act, interpret=interpret)


def _eval_epilogue_fwd(xf, w, a, b, act, interpret):
    z = _eval_epilogue(xf, w, a, b, act, interpret)
    return z, (xf, w, a, z)


def _eval_epilogue_bwd(act, interpret, res, dz):
    xf, w, a, z = res
    dzf = dz.astype(jnp.float32)
    if act == "relu":
        dzf = jnp.where(z > 0, dzf, 0.0)
    dy = dzf * a                                   # z = y·a + b
    wf = w.astype(jnp.float32)
    dx = (dy @ wf.T).astype(xf.dtype)
    y = jnp.dot(xf.astype(jnp.float32), wf)        # recompute, not stored
    dw = (xf.astype(jnp.float32).T @ dy).astype(w.dtype)
    da = jnp.sum(dzf * y, axis=0).astype(a.dtype)
    db = jnp.sum(dzf, axis=0).astype(a.dtype)
    return dx, dw, da, db


_eval_epilogue.defvjp(_eval_epilogue_fwd, _eval_epilogue_bwd)


def fusion_enabled():
    env = os.environ.get("DL4J_TPU_FUSE_CONV_BN")
    if env is None:
        return False
    return env.strip().lower() in ("1", "true", "on", "yes")


def _eligible_conv(layer):
    from deeplearning4j_tpu.nn.conf.layers import ConvolutionLayer
    if type(layer) is not ConvolutionLayer:
        return False
    # explicit nonzero padding would change the output shape of a 1x1
    # conv; the GEMM path only covers pad-free geometry ("same" for k=1
    # is also pad-free)
    pad_free = (str(layer.convolutionMode).lower() == "same"
                or tuple(layer.padding) == (0, 0))
    return (tuple(layer.kernelSize) == (1, 1)
            and tuple(layer.dilation) == (1, 1)
            and layer.stride[0] == layer.stride[1]
            and pad_free
            and not layer.hasBias
            and str(layer.activation).lower() in ("identity", "linear")
            and getattr(layer, "spaceToDepth", 1) == 1
            and not getattr(layer, "frozen", False)
            and not getattr(layer, "frozen_params", False)
            and getattr(layer, "weightNoise", None) is None
            and (layer.dropOut is None or layer.dropOut >= 1.0))


def _eligible_bn(layer):
    from deeplearning4j_tpu.nn.conf.layers import BatchNormalization
    return (type(layer) is BatchNormalization
            and str(layer.activation).lower() in ("identity", "linear",
                                                  "relu")
            and not layer.lockGammaBeta
            and not getattr(layer, "frozen", False)
            and (layer.dropOut is None or layer.dropOut >= 1.0))


def find_conv1x1_bn_fusions(conf):
    """Find eligible (conv1x1 -> batchnorm) node pairs in a built
    ComputationGraphConfiguration.

    Returns {bn_node_name: conv_node_name}. Pure query — the caller
    (ComputationGraph.init) keeps the mapping on the *network instance*,
    never on the shared conf, so two nets built from one conf can run
    fused and unfused independently."""
    nodes = conf.nodes
    consumers = conf.consumers()
    pairs = {}
    for name in conf.topo_order:
        conv = nodes[name]
        if conv.kind != "layer" or not _eligible_conv(conv.ref):
            continue
        outs = consumers.get(name, [])
        if len(outs) != 1 or name in conf.output_names:
            continue
        bn_name = outs[0]
        bn = nodes[bn_name]
        if (bn.kind != "layer" or not _eligible_bn(bn.ref)
                or bn.preprocessor is not None
                or bn_name in conf.output_names
                or len(bn.inputs) != 1):
            continue
        pairs[bn_name] = name
    return pairs


def fused_apply(conv_layer, bn_layer, p_conv, p_bn, s_bn, x, train,
                interpret=None):
    """Execute act(batchnorm(conv1x1(x))) fused. x: (B, H, W, C) NHWC.

    Returns (z, new_bn_state, y_conv) with semantics identical to running
    conv_layer.apply then bn_layer.apply in train/eval mode; y_conv is
    the intermediate conv output (already materialized by the kernel —
    the graph records it so feedForward() still reports the conv node's
    true activation)."""
    s = conv_layer.stride[0]
    if s > 1:
        # 1x1 conv with stride s touches exactly the (::s, ::s) pixels
        x = x[:, ::s, ::s, :]
    b, h, w_, cin = x.shape
    w = p_conv["W"].astype(x.dtype).reshape(cin, -1)
    n = w.shape[1]
    xf = x.reshape(b * h * w_, cin)
    if train:
        from deeplearning4j_tpu.kernels.pointwise_conv import (
            fused_conv1x1_bn, matmul_stats)
        gamma = p_bn.get("gamma")
        beta = p_bn.get("beta")
        act = str(bn_layer.activation).lower()
        act = "identity" if act in ("identity", "linear") else act
        z, mu, var = fused_conv1x1_bn(xf, w, gamma, beta, bn_layer.eps,
                                      act, interpret)
        d = bn_layer.decay
        new_state = {"mean": d * s_bn["mean"] + (1 - d) * mu,
                     "var": d * s_bn["var"] + (1 - d) * var}
        # conv activation for feedForward reporting: recompute lazily —
        # XLA DCEs this whole branch unless someone actually reads it
        y = jnp.dot(xf, w, preferred_element_type=jnp.float32).astype(
            x.dtype)
    elif isinstance(xf, jax.core.Tracer):
        # jitted inference (serving/eval executables): BN is a
        # per-channel affine of the RUNNING stats — fold it (plus the
        # relu) into the GEMM's epilogue so the conv output tile is
        # normalized while still in VMEM instead of in a standalone
        # BN-apply pass (the shape the round-3 chip runs concluded is the
        # only fusion that wins). _eval_epilogue carries a custom VJP
        # (recompute-based closed form), so autodiff THROUGH an eval
        # forward (input saliency etc.) keeps working. The
        # reporting-only y below is DCE'd by XLA unless something
        # actually reads it.
        gamma = p_bn.get("gamma", jnp.ones_like(s_bn["mean"]))
        beta = p_bn.get("beta", jnp.zeros_like(s_bn["mean"]))
        inv = jax.lax.rsqrt(s_bn["var"] + bn_layer.eps)
        act = str(bn_layer.activation).lower()
        act = "identity" if act in ("identity", "linear") else act
        z = _eval_epilogue(xf, w, gamma * inv,
                           beta - gamma * s_bn["mean"] * inv,
                           act, interpret)
        new_state = s_bn
        y = jnp.dot(xf, w, preferred_element_type=jnp.float32).astype(
            x.dtype)
    else:
        # eager inference: nothing DCEs an unread tensor here, so a
        # separate epilogue kernel would make the conv GEMM run twice
        # (once for z, once for the reported y) — one GEMM + the
        # standalone BN apply is strictly cheaper op-by-op
        y = jnp.dot(xf, w, preferred_element_type=jnp.float32).astype(
            x.dtype)
        z, new_state = bn_layer.apply(p_bn, s_bn, y, train=False)
    return (z.reshape(b, h, w_, n), new_state,
            y.reshape(b, h, w_, n))
