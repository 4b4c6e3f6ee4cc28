"""Updaters (≡ nd4j-api :: learning.config.IUpdater: Sgd, Adam, AdaMax,
Nadam, AMSGrad, Nesterovs, RmsProp, AdaGrad, AdaDelta, NoOp).

Each updater lowers to an optax GradientTransformation; the whole update is
part of the single jitted train step (the reference dispatches a separate
updater CUDA kernel per parameter — here XLA fuses it with the backward
pass). Schedules (nn.schedules) pass through as optax-style callables.
"""
from __future__ import annotations

import optax

from deeplearning4j_tpu.nn.schedules import Schedule, as_schedule


def _lr(value):
    sched = as_schedule(value)
    if isinstance(sched, Schedule):
        return lambda step: sched(step)
    return sched


class Updater:
    def to_optax(self):
        raise NotImplementedError

    def config(self):
        return {"type": type(self).__name__, **self.__dict__}


def same_updater(a, b):
    """Structural equality (identity breaks after config JSON roundtrip)."""
    return a is b or (type(a) is type(b)
                      and getattr(a, "__dict__", None) == getattr(
                          b, "__dict__", None))


class Sgd(Updater):
    def __init__(self, learningRate=0.1):
        self.learningRate = learningRate

    def to_optax(self):
        return optax.sgd(_lr(self.learningRate))


class Nesterovs(Updater):
    """≡ learning.config.Nesterovs. `momentumDtype="bfloat16"` keeps the
    momentum buffer in bf16 — halves the optimizer-state HBM traffic per
    step on TPU (the ResNet step is HBM-bound). Parameters
    stay fp32 masters; only the velocity accumulator is cast."""

    def __init__(self, learningRate=0.1, momentum=0.9, momentumDtype=None):
        self.learningRate, self.momentum = learningRate, momentum
        self.momentumDtype = momentumDtype

    def to_optax(self):
        acc = None
        if self.momentumDtype is not None:
            import jax.numpy as jnp

            acc = jnp.dtype(self.momentumDtype)
        return optax.sgd(_lr(self.learningRate), momentum=self.momentum,
                         nesterov=True, accumulator_dtype=acc)


class Adam(Updater):
    def __init__(self, learningRate=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8):
        self.learningRate, self.beta1, self.beta2, self.epsilon = learningRate, beta1, beta2, epsilon

    def to_optax(self):
        return optax.adam(_lr(self.learningRate), b1=self.beta1, b2=self.beta2, eps=self.epsilon)


class AdaMax(Adam):
    def to_optax(self):
        return optax.adamax(_lr(self.learningRate), b1=self.beta1, b2=self.beta2, eps=self.epsilon)


class Nadam(Adam):
    def to_optax(self):
        return optax.nadam(_lr(self.learningRate), b1=self.beta1, b2=self.beta2, eps=self.epsilon)


class AMSGrad(Adam):
    def to_optax(self):
        return optax.amsgrad(_lr(self.learningRate), b1=self.beta1, b2=self.beta2, eps=self.epsilon)


class RmsProp(Updater):
    def __init__(self, learningRate=1e-1, rmsDecay=0.95, epsilon=1e-8):
        self.learningRate, self.rmsDecay, self.epsilon = learningRate, rmsDecay, epsilon

    def to_optax(self):
        return optax.rmsprop(_lr(self.learningRate), decay=self.rmsDecay, eps=self.epsilon)


class AdaGrad(Updater):
    def __init__(self, learningRate=1e-1, epsilon=1e-6):
        self.learningRate, self.epsilon = learningRate, epsilon

    def to_optax(self):
        return optax.adagrad(_lr(self.learningRate), eps=self.epsilon)


class AdaDelta(Updater):
    def __init__(self, rho=0.95, epsilon=1e-6):
        self.rho, self.epsilon = rho, epsilon

    def to_optax(self):
        return optax.adadelta(rho=self.rho, eps=self.epsilon)


class NoOp(Updater):
    def to_optax(self):
        return optax.set_to_zero()


class GradientNormalization:
    NONE = "none"
    RENORMALIZE_L2_PER_LAYER = "renormalizel2perlayer"
    CLIP_ELEMENTWISE_ABSOLUTE_VALUE = "clipelementwiseabsolutevalue"
    CLIP_L2_PER_LAYER = "clipl2perlayer"
    CLIP_L2_PER_PARAM_TYPE = "clipl2perparamtype"


def build_optimizer(updater, gradient_normalization=None,
                    gradient_normalization_threshold=1.0,
                    weight_decay=0.0):
    """Chain gradient normalization (≡ GradientNormalization enum) +
    decoupled weightDecay + the updater into one optax transform."""
    import jax
    import jax.numpy as jnp

    chain = []
    gn = (gradient_normalization or "none").lower().replace("_", "")
    thr = float(gradient_normalization_threshold)
    if gn in ("clipelementwiseabsolutevalue",):
        chain.append(optax.clip(thr))
    elif gn in ("clipl2perlayer", "clipl2perparamtype"):
        # per-leaf L2 clip (param-type granularity: each leaf is one
        # parameter tensor, matching the reference's per-param-type clip)
        def per_leaf_clip(updates, state, params=None):
            del params
            def clipleaf(g):
                n = jnp.sqrt(jnp.sum(g * g) + 1e-12)
                return g * jnp.minimum(1.0, thr / n)
            return jax.tree_util.tree_map(clipleaf, updates), state
        chain.append(optax.GradientTransformation(lambda p: optax.EmptyState(), per_leaf_clip))
    elif gn in ("renormalizel2perlayer",):
        def renorm(updates, state, params=None):
            del params
            def norml(g):
                n = jnp.sqrt(jnp.sum(g * g) + 1e-12)
                return g / n
            return jax.tree_util.tree_map(norml, updates), state
        chain.append(optax.GradientTransformation(lambda p: optax.EmptyState(), renorm))
    elif gn in ("none",):
        pass
    else:
        raise ValueError(f"Unknown GradientNormalization '{gradient_normalization}'")

    if weight_decay:
        chain.append(optax.add_decayed_weights(float(weight_decay)))
    chain.append(updater.to_optax() if isinstance(updater, Updater) else updater)
    return optax.chain(*chain) if len(chain) > 1 else chain[0]
