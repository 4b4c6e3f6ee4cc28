"""MultiLayerNetwork (≡ deeplearning4j-nn :: multilayer.MultiLayerNetwork).

The reference drives fit() through a Solver that executes ops one-by-one on
the CUDA executioner with cuDNN helper hand-offs; here the WHOLE training
step — forward, loss (+ L1/L2), backward, gradient normalization, updater —
traces into ONE jitted XLA executable with donated param/optimizer buffers,
which is the TPU-native equivalent of the reference's workspace reuse +
fused helper path. Inputs are cast to the configured compute dtype
(`dataType`, e.g. bfloat16 for MXU) while parameters stay float32 masters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu import monitoring as _mon
from deeplearning4j_tpu.monitoring import profiler as _prof
from deeplearning4j_tpu.resilience import faults as _faults
from deeplearning4j_tpu.resilience import guardian as _guardian
from deeplearning4j_tpu.resilience import watchdog as _watchdog
from deeplearning4j_tpu.runtime import pipeline as _pipeline
from deeplearning4j_tpu.util.crash_reporting import \
    with_crash_dump
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn import accum as _accum
from deeplearning4j_tpu.nn.updaters import Updater, build_optimizer, same_updater
from deeplearning4j_tpu.ops.ndarray import NDArray, as_jax, resolve_dtype


def _l1l2_penalty(layer_confs, params):
    """≡ reference score regularization: l1*sum|W| + 0.5*l2*||W||² on weight
    tensors (biases/beta/gamma excluded, matching the reference)."""
    total = 0.0
    for i, layer in enumerate(layer_confs):
        l1, l2 = layer.regularization_terms()
        if not l1 and not l2:
            continue
        p = params.get(str(i), {})
        for name, v in p.items():
            if name in ("b", "beta", "gamma", "alpha", "centers"):
                continue
            v = v.astype(jnp.float32)
            if l1:
                total += l1 * jnp.sum(jnp.abs(v))
            if l2:
                total += 0.5 * l2 * jnp.sum(v * v)
    return total


def _hook_params(layer, p, ltrain, lrng):
    """Per-layer param transforms shared by BOTH network classes' forward
    loops (MultiLayerNetwork and ComputationGraph must never diverge):
    - frozen_params (≡ FrozenLayerWithBackprop): params are constants to
      the grad; train-mode behavior and upstream gradients kept.
    - weightNoise (WeightNoise/DropConnect): weight-space noise as a pure
      function of the step rng — stays inside the jitted step. The 0x57
      fold_in tag keeps the noise stream distinct from the layer's
      dropout stream (which uses lrng directly)."""
    if getattr(layer, "frozen_params", False):
        p = jax.tree_util.tree_map(jax.lax.stop_gradient, p)
    wn = getattr(layer, "weightNoise", None)
    if wn is not None and ltrain and lrng is not None:
        p = wn.apply_to_params(p, jax.random.fold_in(lrng, 0x57))
    return p


def _apply_layer(layer, p, s, x, ltrain, lrng, mask):
    """Run one layer, honouring its `remat` flag: remat=True wraps the
    train-mode apply in jax.checkpoint so activations inside the layer are
    recomputed during backward instead of stored — the DSL-level knob for
    trading FLOPs against HBM on deep/long-sequence models (any layer
    config accepts remat=True / .remat(True); ≡ the role of the
    reference's workspace memory modes, but as a per-layer rematerialization
    policy the XLA way)."""
    if ltrain and getattr(layer, "remat", False):
        def inner(p_, s_, x_, r_, m_):
            return layer.apply(p_, s_, x_, train=True, rng=r_, mask=m_)
        return jax.checkpoint(inner)(p, s, x, lrng, mask)
    return layer.apply(p, s, x, train=ltrain, rng=lrng, mask=mask)


class MultiLayerNetwork:
    def __init__(self, conf):
        self.conf = conf
        self.layers = conf.layers
        self._params = None
        self._state = None
        self._opt_state = None
        self._tx = None
        self._listeners = []
        self._score = None
        self._iteration = 0
        self._epoch = 0
        self._compute_dtype = resolve_dtype(conf.data_type) or jnp.float32
        self._rng_key = jax.random.PRNGKey(conf.seed)

    # -- lifecycle -------------------------------------------------------
    def init(self, params=None):
        if self.conf.input_type is None:
            raise ValueError("setInputType(...) (or explicit nIn on every "
                             "layer) is required before init()")
        key = jax.random.PRNGKey(self.conf.seed)
        ps, ss = {}, {}
        cur = self.conf.input_type
        from deeplearning4j_tpu.nn.conf.inputs import ConvolutionalFlatType
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        if isinstance(cur, ConvolutionalFlatType):
            cur = InputType.feedForward(cur.arrayElementsPerExample())
        for i, layer in enumerate(self.layers):
            in_type = self.conf.input_types[i]
            key, sub = jax.random.split(key)
            p, s, cur = layer.initialize(sub, in_type)
            if p:
                ps[str(i)] = p
            if s:
                ss[str(i)] = s
        self._params = ps
        self._state = ss
        if params is not None:
            self.setParams(params)
        self._build_optimizer()
        return self

    def _build_optimizer(self):
        defaults = self.conf.defaults
        global_updater = defaults.get("updater")
        overrides = {str(i): l.updater for i, l in enumerate(self.layers)
                     if l.updater is not None
                     and not same_updater(l.updater, global_updater)}
        gn = defaults.get("gradientNormalization")
        gn_thr = defaults.get("gradientNormalizationThreshold", 1.0)
        wd = defaults.get("weightDecay", 0.0) or 0.0
        if not overrides:
            self._tx = build_optimizer(global_updater, gn, gn_thr, wd)
        else:
            transforms = {"__global__": build_optimizer(global_updater, gn, gn_thr, wd)}
            for k, u in overrides.items():
                transforms[k] = build_optimizer(u, gn, gn_thr, wd)
            labels = {k: (k if k in overrides else "__global__")
                      for k in self._params}
            self._tx = optax.multi_transform(transforms, labels)
        self._opt_state = self._tx.init(self._params)

    # -- parameter surface (≡ Model.params()/numParams/paramTable) ------
    def paramTable(self):
        flat = {}
        for li, p in (self._params or {}).items():
            for name, v in p.items():
                flat[f"{li}_{name}"] = NDArray(v)
        return flat

    def params(self):
        leaves = jax.tree_util.tree_leaves(
            {k: self._params[k] for k in sorted(self._params, key=int)})
        if not leaves:
            return NDArray(jnp.zeros((0,)))
        return NDArray(jnp.concatenate([l.ravel() for l in leaves]))

    def numParams(self):
        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self._params))

    def setParams(self, flat):
        flat = as_jax(flat).ravel()
        ordered = {k: self._params[k] for k in sorted(self._params, key=int)}
        leaves, treedef = jax.tree_util.tree_flatten(ordered)
        out, off = [], 0
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(flat[off:off + n].reshape(l.shape).astype(l.dtype))
            off += n
        rebuilt = jax.tree_util.tree_unflatten(treedef, out)
        self._params = {k: rebuilt[k] for k in self._params}
        return self

    def getParam(self, key):
        li, name = key.split("_", 1)
        return NDArray(self._params[li][name])

    def setParam(self, key, value):
        li, name = key.split("_", 1)
        self._params[li][name] = as_jax(value).astype(self._params[li][name].dtype)

    # -- forward ---------------------------------------------------------
    def _forward(self, params, state, x, train, rng, mask=None,
                 collect=False, stop_at=None, carries=None):
        """carries: optional {layer_idx: carry} for TBPTT / rnnTimeStep —
        recurrent layers are then driven via scan_apply so hidden state
        threads across calls (≡ the reference's rnnActivateUsingStoredState)."""
        x = x.astype(self._compute_dtype)
        acts = []
        new_state = dict(state)
        new_carries = {} if carries is not None else None
        preact = None
        n = len(self.layers) if stop_at is None else stop_at
        for i, layer in enumerate(self.layers[:n]):
            # frozen layers (transfer learning) always run inference-mode:
            # no dropout, batch-norm running stats pinned (≡ FrozenLayer)
            ltrain = train and not getattr(layer, "frozen", False)
            pp = self.conf.preprocessors.get(i)
            if pp is not None:
                x = pp.preProcess(x)
            lrng = None
            if rng is not None:
                lrng = jax.random.fold_in(rng, i)
            p = _hook_params(layer, params.get(str(i), {}), ltrain, lrng)
            s = state.get(str(i), {})
            if i == len(self.layers) - 1 and hasattr(layer, "compute_loss") \
                    and hasattr(layer, "pre_activation"):
                xd = layer._dropout_in(x, ltrain, lrng)
                if getattr(layer, "pre_activation_takes_mask", False):
                    # custom loss heads (SameDiffOutputLayer) keep the
                    # defineLayer(params, x, mask) contract
                    preact = layer.pre_activation(p, xd, mask=mask)
                else:
                    preact = layer.pre_activation(p, xd)
                from deeplearning4j_tpu.nn.activations import get_activation
                x = get_activation(layer.activation)(preact)
            elif carries is not None and getattr(layer, "is_recurrent", False):
                if not hasattr(layer, "scan_apply"):
                    raise ValueError(
                        f"rnnTimeStep/tbptt: {type(layer).__name__} (layer "
                        f"{i}) cannot run step-by-step (no carried state "
                        "protocol); use fit/output on whole sequences")
                x = layer._dropout_in(x, ltrain, lrng)
                x, carry = layer.scan_apply(p, x, carries.get(str(i)), mask)
                new_carries[str(i)] = carry
            else:
                x, ns = _apply_layer(layer, p, s, x, ltrain, lrng, mask)
                if ns:
                    new_state[str(i)] = ns
            if mask is not None:
                # layers that reshape/drop the time axis transform the mask
                # for everything downstream (≡ feedForwardMaskArray)
                mask = layer.feed_forward_mask(mask)
            if collect:
                acts.append(x)
        if carries is not None:
            return x, preact, new_state, acts, new_carries
        return x, preact, new_state, acts

    @with_crash_dump
    def output(self, x, train=False, fmask=None):
        x = as_jax(x)
        fmask = None if fmask is None else as_jax(fmask)
        y, _, _, _ = self._forward(self._params, self._state, x, train, None,
                                   mask=fmask)
        return NDArray(y)

    def getOutputLayer(self):
        """≡ MultiLayerNetwork.getOutputLayer — the last layer's conf
        object (e.g. a Yolo2OutputLayer for detection post-processing)."""
        return self.layers[-1]

    def getPredictedObjects(self, x, confThreshold=0.5, nmsThreshold=0.4):
        """Detection convenience (≡ YoloUtils.getPredictedObjects over
        this net's output): forward + decode + threshold + per-class NMS.
        Returns List[List[DetectedObject]], one inner list per example.
        Requires the output layer to be a Yolo2OutputLayer."""
        out_layer = self.layers[-1]
        if not hasattr(out_layer, "getPredictedObjects"):
            raise TypeError(
                f"output layer {type(out_layer).__name__} has no detection "
                "decode — getPredictedObjects needs a Yolo2OutputLayer head")
        y = self.output(x)
        return out_layer.getPredictedObjects(as_jax(y), confThreshold,
                                             nmsThreshold)

    def predict(self, x):
        """≡ Classifier.predict — argmax class index per example."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        if isinstance(x, DataSet):
            x = x.features
        out = self.output(x).numpy()
        return np.argmax(out, axis=-1)

    def f1Score(self, data, labels=None):
        """≡ Classifier.f1Score(DataSet | (examples, labels)) —
        macro-averaged F1 (Evaluation.f1()'s default) over one forward
        pass."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        mask = None
        if isinstance(data, DataSet):
            feats, labels = data.features, data.labels
            mask = data.labelsMask
        else:
            feats = data
        ev = Evaluation()
        ev.eval(labels, self.output(feats).numpy(), mask)
        return ev.f1()

    def feedForward(self, x, train=False):
        x = as_jax(x)
        _, _, _, acts = self._forward(self._params, self._state, x, train,
                                      None, collect=True)
        return [NDArray(a) for a in acts]

    def activateSelectedLayers(self, from_idx, to_idx, x):
        """Apply layers [from_idx, to_idx] inclusive to activations `x`
        (which must already be layer from_idx's input)."""
        x = as_jax(x).astype(self._compute_dtype)
        for i in range(int(from_idx), int(to_idx) + 1):
            layer = self.layers[i]
            pp = self.conf.preprocessors.get(i)
            if pp is not None:
                x = pp.preProcess(x)
            x, _ = layer.apply(self._params.get(str(i), {}),
                               self._state.get(str(i), {}), x, train=False)
        return NDArray(x)

    # -- stateful RNN inference (≡ rnnTimeStep/rnnClearPreviousState) ----
    def rnnTimeStep(self, x):
        x = as_jax(x)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]  # (B, F) -> (B, 1, F)
        if not hasattr(self, "_rnn_carries") or self._rnn_carries is None:
            self._rnn_carries = {}
        y, _, _, _, self._rnn_carries = self._forward(
            self._params, self._state, x, False, None,
            carries=self._rnn_carries)
        return NDArray(y[:, -1, :] if squeeze and y.ndim == 3 else y)

    def rnnClearPreviousState(self):
        self._rnn_carries = None

    def rnnGetPreviousState(self, layer_idx):
        return (self._rnn_carries or {}).get(str(layer_idx))

    # -- loss / gradients -------------------------------------------------
    def _loss(self, params, state, x, y, fmask, lmask, rng, carries=None,
              train=True):
        out_layer = self.layers[-1]
        if not hasattr(out_layer, "compute_loss"):
            raise ValueError("Last layer must be an OutputLayer/LossLayer to fit()")
        needs_feats = getattr(out_layer, "needs_features", False)
        if needs_feats and carries is not None:
            raise ValueError(
                f"{type(out_layer).__name__} (feature-dependent loss) is "
                "not supported with truncated BPTT")
        if carries is not None:
            _, preact, new_state, _, new_carries = self._forward(
                params, state, x, train, rng, mask=fmask, carries=carries)
        else:
            _, preact, new_state, acts = self._forward(
                params, state, x, train, rng, mask=fmask,
                collect=needs_feats)
            new_carries = None
        if needs_feats and carries is None:
            feats = acts[-2] if len(acts) >= 2 else x.astype(
                self._compute_dtype)
            pp = self.conf.preprocessors.get(len(self.layers) - 1)
            if pp is not None:
                feats = pp.preProcess(feats)
            data_loss = out_layer.compute_loss_with_features(
                params.get(str(len(self.layers) - 1), {}),
                y.astype(jnp.float32), preact.astype(jnp.float32),
                feats.astype(jnp.float32), lmask)
        else:
            data_loss = out_layer.compute_loss(y.astype(jnp.float32),
                                               preact.astype(jnp.float32),
                                               lmask)
        return (data_loss + _l1l2_penalty(self.layers, params),
                (new_state, new_carries))

    def score(self, dataset=None):
        if dataset is not None:
            x, y = as_jax(dataset.features), as_jax(dataset.labels)
            fmask = None if dataset.featuresMask is None else as_jax(dataset.featuresMask)
            lmask = None if dataset.labelsMask is None else as_jax(dataset.labelsMask)
            # inference-mode forward (BN running stats, no dropout) —
            # matches the reference's score(DataSet) semantics
            loss, _ = self._loss(self._params, self._state, x, y, fmask,
                                 lmask, None, train=False)
            return float(loss)
        # lazy score: fit() leaves the DEVICE loss scalar in _score so a
        # listener-free loop never blocks; reading it here is the
        # on-demand sync point (counted via dl4j.pipeline.syncs)
        return _pipeline.materialize_score(self)

    def computeGradients(self, x, y, fmask=None, lmask=None):
        """Gradients of the full regularized loss — used by gradient-check
        tests (≡ deeplearning4j-core GradientCheckUtil)."""
        x, y = as_jax(x), as_jax(y)
        grads, _ = jax.grad(
            lambda p: self._loss(p, self._state, x, y, fmask, lmask, None),
            has_aux=True)(self._params)
        return grads

    # -- training ---------------------------------------------------------
    def _apply_constraints(self, params):
        """Post-update parameter constraints (≡ BaseConstraint application
        after the updater step) — folded into the jitted step; free when no
        layer declares constraints (static config, checked at trace)."""
        pairs = [(str(i), l) for i, l in enumerate(self.layers)]
        if not any(getattr(l, "constraints", None) for _, l in pairs):
            return params
        from deeplearning4j_tpu.nn.constraints import apply_layer_constraints
        return apply_layer_constraints(pairs, params)

    @functools.cached_property
    def _train_step(self):
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def multilayer_train_step(params, opt_state, state, x, y, fmask, lmask,
                                  rng):
            (loss, (new_state, _)), grads = jax.value_and_grad(
                lambda p: self._loss(p, state, x, y, fmask, lmask, rng),
                has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params = self._apply_constraints(params)
            return params, opt_state, new_state, loss

        return multilayer_train_step

    @functools.cached_property
    def _train_step_guarded(self):
        """The guardian's variant of `_train_step`: the SAME update plus
        a device-side health verdict — global grad norm finite, loss
        finite, grad norm under the guardian's EMA-derived threshold —
        and the update is APPLIED ONLY WHEN HEALTHY (`jnp.where`
        select inside the same donated program), so one overflowing
        step can never write NaN into the live params. `lr_scale`
        (traced scalar — no recompile when the guardian backs off the
        LR) multiplies the updates for the reduce-LR escalation rung.
        Compiled only when a guardian is installed; the unguarded path
        is untouched."""
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def multilayer_train_step_guarded(params, opt_state, state, x, y,
                                          fmask, lmask, rng, lr_scale,
                                          max_gnorm):
            (loss, (new_state, _)), grads = jax.value_and_grad(
                lambda p: self._loss(p, state, x, y, fmask, lmask, rng),
                has_aux=True)(params)
            params, opt_state, (state,), gnorm, ok = \
                _guardian.guarded_apply(
                    tx, grads, loss, params, opt_state, lr_scale,
                    max_gnorm, constraints=self._apply_constraints,
                    extra=((new_state, state),))
            return params, opt_state, state, loss, gnorm, ok

        return multilayer_train_step_guarded

    @functools.cached_property
    def _train_scan(self):
        """K train steps in ONE dispatch: lax.scan over stacked batches.

        TPU-first replacement for the reference's per-batch fit loop
        (MultiLayerNetwork.fit → one Solver step per DataSet): every
        dispatch costs a host round-trip, which can dominate sub-20 ms
        steps (not yet re-measured on a directly attached chip). The
        scan body is the SAME update as _train_step, consuming one stacked
        batch slice and one pre-split rng per iteration, so k scanned steps
        are bit-identical to k sequential _train_step calls."""
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def multilayer_train_step_scan(params, opt_state, state, xs, ys,
                                       fmasks, lmasks, rngs):
            def body(carry, inp):
                p, o, s = carry
                x, y, fm, lm, rng = inp
                (loss, (ns, _)), grads = jax.value_and_grad(
                    lambda pp: self._loss(pp, s, x, y, fm, lm, rng),
                    has_aux=True)(p)
                updates, o = tx.update(grads, o, p)
                p = optax.apply_updates(p, updates)
                p = self._apply_constraints(p)
                return (p, o, ns), loss

            (params, opt_state, state), losses = jax.lax.scan(
                body, (params, opt_state, state),
                (xs, ys, fmasks, lmasks, rngs))
            return params, opt_state, state, losses

        return multilayer_train_step_scan

    def _fit_batches_scanned(self, group):
        """Flush a same-shape batch group through ONE scanned dispatch.
        Callers only send FULL groups here (sub-k remainders run singly)
        so lax.scan is traced for exactly one length per batch shape."""
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.TRAIN_DISPATCH)
        if _watchdog.ACTIVE is not None:
            _watchdog.ACTIVE.beat(f"multilayer@{id(self):x}")
        _ps = _prof.ACTIVE             # armed ProfileSession: the whole
        if _ps is not None:            # scanned dispatch is one "step"
            _ps.step_start()
        with _mon.span("train.stage"):
            subs = []
            for _ in group:   # identical key stream to seq _fit_batch
                self._rng_key, sub = jax.random.split(self._rng_key)
                subs.append(sub)
            xs = jnp.stack([jnp.asarray(f) for f, _, _, _ in group])
            ys = jnp.stack([jnp.asarray(l) for _, l, _, _ in group])
            lms = (None if group[0][2] is None
                   else jnp.stack([jnp.asarray(m)
                                   for _, _, m, _ in group]))
            fms = (None if group[0][3] is None
                   else jnp.stack([jnp.asarray(m)
                                   for _, _, _, m in group]))
        with _mon.span("train.scan_dispatch"):
            (self._params, self._opt_state, self._state,
             losses) = self._train_scan(self._params, self._opt_state,
                                        self._state, xs, ys, fms, lms,
                                        jnp.stack(subs))
        self._last_features = group[-1][0]
        self._params_version = getattr(self, "_params_version", 0) + 1
        with _mon.span("train.listeners"):
            if self._listeners:
                # device slices, not device_get: listeners that never
                # read score() cost zero syncs; ones that do pay only
                # for the iterations they actually look at
                for i in range(len(group)):
                    self._score = losses[i]
                    self._iteration += 1
                    for listener in self._listeners:
                        listener.iterationDone(self, self._iteration,
                                               self._epoch)
            else:
                self._score = losses[len(group) - 1]
                self._iteration += len(group)
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_end()

    # -- in-step gradient accumulation (ISSUE 14): G microbatches ->
    # ONE optimizer step in ONE dispatch. Unlike _train_scan (k separate
    # updates), the scan body only accumulates gradients; the single
    # update runs after the scan — so a G-microbatch step equals an
    # on-device sequential sum-then-update reference, and the effective
    # batch is G× the per-dispatch memory footprint.
    @functools.cached_property
    def _train_step_accum(self):
        """Accumulated step: `nn/accum.accum_scan` over G stacked
        microbatches (grads/loss summed on device, BN state threaded
        sequentially), then ONE updater application."""
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def multilayer_train_step_accum(params, opt_state, state, xs, ys,
                                        fmasks, lmasks, rngs):
            grads, loss, _, state = _accum.accum_scan(
                self._accum_grad_fn, params, state,
                (xs, ys, fmasks, lmasks, rngs))
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params = self._apply_constraints(params)
            return params, opt_state, state, loss

        return multilayer_train_step_accum

    def _accum_grad_fn(self, params, state, inp):
        """One microbatch's ((loss, new_state), grads) for accum_scan
        (drops the per-layer activations aux the plain step keeps)."""
        x, y, fm, lm, rng = inp
        (loss, (ns, _)), grads = jax.value_and_grad(
            lambda p: self._loss(p, state, x, y, fm, lm, rng),
            has_aux=True)(params)
        return (loss, ns), grads

    @functools.cached_property
    def _train_step_accum_guarded(self):
        """Guardian variant of `_train_step_accum`: ONE device health
        verdict gates the ACCUMULATED update (params, optimizer state
        and bn state all revert when unhealthy), while a NaN in any
        single microbatch still fails it — per-microbatch loss
        finiteness is ANDed through the scan and poisons the loss the
        verdict inspects (non-finite grads also survive the on-device
        sum into the accumulated gnorm). Unlike stepsPerDispatch (which
        the guardian forces to 1: a scan group hides k-1 verdicts),
        accumulation IS one optimizer step — one verdict is exactly the
        per-update cadence the guardian needs."""
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def multilayer_train_step_accum_guarded(params, opt_state, state, xs,
                                                ys, fmasks, lmasks, rngs,
                                                lr_scale, max_gnorm):
            grads, loss, micro_ok, new_state = _accum.accum_scan(
                self._accum_grad_fn, params, state,
                (xs, ys, fmasks, lmasks, rngs))
            vloss = jnp.where(micro_ok, loss, jnp.float32(jnp.nan))
            params, opt_state, (state,), gnorm, ok = \
                _guardian.guarded_apply(
                    tx, grads, vloss, params, opt_state, lr_scale,
                    max_gnorm, constraints=self._apply_constraints,
                    extra=((new_state, state),))
            return params, opt_state, state, loss, gnorm, ok

        return multilayer_train_step_accum_guarded

    def _fit_batches_accum(self, group):
        """Flush a FULL G-batch group through one accumulated optimizer
        step. One REAL update: iteration count and listeners advance
        once (the group is one step of the G×-effective batch), score
        is the mean microbatch loss (device scalar, lazy)."""
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.TRAIN_DISPATCH)
        if _watchdog.ACTIVE is not None:
            _watchdog.ACTIVE.beat(f"multilayer@{id(self):x}")
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_start()
        with _mon.span("train.stage"):
            subs = []
            for _ in group:   # one split per microbatch, like the scan
                self._rng_key, sub = jax.random.split(self._rng_key)
                subs.append(sub)
            xs = jnp.stack([jnp.asarray(f) for f, _, _, _ in group])
            ys = jnp.stack([jnp.asarray(l) for _, l, _, _ in group])
            lms = (None if group[0][2] is None
                   else jnp.stack([jnp.asarray(m)
                                   for _, _, m, _ in group]))
            fms = (None if group[0][3] is None
                   else jnp.stack([jnp.asarray(m)
                                   for _, _, _, m in group]))
        _g = _guardian.ACTIVE
        with _mon.span("train.accum_dispatch"):
            if _g is not None:
                (self._params, self._opt_state, self._state, loss,
                 gnorm, ok) = self._train_step_accum_guarded(
                    self._params, self._opt_state, self._state, xs, ys,
                    fms, lms, jnp.stack(subs), _g.lr_scale,
                    _g.max_gnorm)
            else:
                (self._params, self._opt_state, self._state,
                 loss) = self._train_step_accum(
                    self._params, self._opt_state, self._state, xs, ys,
                    fms, lms, jnp.stack(subs))
            self._score = loss    # device scalar; score() floats it
        if _g is not None:
            _g.on_step(loss, gnorm, ok)   # one verdict per real update
        self._iteration += 1
        self._last_features = group[-1][0]
        self._params_version = getattr(self, "_params_version", 0) + 1
        with _mon.span("train.listeners"):
            for listener in self._listeners:
                listener.iterationDone(self, self._iteration, self._epoch)
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_end()

    @staticmethod
    def _batch_sig(ds):
        def sig(a):
            return None if a is None else tuple(np.shape(a))
        return (sig(ds.features), sig(ds.labels), sig(ds.labelsMask),
                sig(ds.featuresMask))

    @functools.cached_property
    def _train_step_tbptt(self):
        """TBPTT segment step: gradients truncate at segment boundaries,
        hidden state (carries) threads across segments
        (≡ BackpropType.TruncatedBPTT in the reference)."""
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def multilayer_train_step_tbptt(params, opt_state, state, carries, x,
                                        y, fmask, lmask, rng):
            def lossf(p):
                loss, (new_state, new_carries) = self._loss(
                    p, state, x, y, fmask, lmask, rng, carries=carries)
                return loss, (new_state, new_carries)
            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                lossf, has_aux=True)(params)
            # stop state flowing gradients across segments
            new_carries = jax.lax.stop_gradient(new_carries)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params = self._apply_constraints(params)
            return params, opt_state, new_state, new_carries, loss

        return multilayer_train_step_tbptt

    @functools.cached_property
    def _train_step_tbptt_guarded(self):
        """Guardian variant of `_train_step_tbptt`: the same segment
        update plus the device-side health verdict, applied only when
        healthy — params, optimizer state, bn state AND the recurrent
        carries (a NaN forward pass must not poison the hidden state
        that threads into the next segment). Segments report
        `on_step(retryable=False)`: earlier healthy segments of the same
        batch already updated params, so the RETRY rung must never
        re-run the whole batch."""
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def multilayer_train_step_tbptt_guarded(params, opt_state, state,
                                                carries, x, y, fmask, lmask,
                                                rng, lr_scale, max_gnorm):
            def lossf(p):
                loss, (new_state, new_carries) = self._loss(
                    p, state, x, y, fmask, lmask, rng, carries=carries)
                return loss, (new_state, new_carries)
            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                lossf, has_aux=True)(params)
            # stop state flowing gradients across segments
            new_carries = jax.lax.stop_gradient(new_carries)
            params, opt_state, (state, carries), gnorm, ok = \
                _guardian.guarded_apply(
                    tx, grads, loss, params, opt_state, lr_scale,
                    max_gnorm, constraints=self._apply_constraints,
                    extra=((new_state, state), (new_carries, carries)))
            return params, opt_state, state, carries, loss, gnorm, ok

        return multilayer_train_step_tbptt_guarded

    def _zero_carries(self, batch):
        carries = {}
        for i, layer in enumerate(self.layers):
            if getattr(layer, "is_recurrent", False) and hasattr(layer, "zero_carry"):
                carries[str(i)] = layer.zero_carry(batch, self._compute_dtype)
        return carries

    def _fit_batch(self, features, labels, labels_mask=None,
                   features_mask=None):
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.TRAIN_DISPATCH)
        if _watchdog.ACTIVE is not None:
            _watchdog.ACTIVE.beat(f"multilayer@{id(self):x}")
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_start()
        # "train.stage": host-side step prep (device placement of the
        # batch + rng split) — its own attribution phase so the flight
        # recorder's per-step sum tracks wall time (steps.SUM_PHASES)
        with _mon.span("train.stage"):
            x = jnp.asarray(features)
            y = jnp.asarray(labels)
            lmask = None if labels_mask is None \
                else jnp.asarray(labels_mask)
            fmask = None if features_mask is None \
                else jnp.asarray(features_mask)
            self._rng_key, sub = jax.random.split(self._rng_key)
        from deeplearning4j_tpu.nn.conf.builders import BackpropType
        if (self.conf.backprop_type == BackpropType.TruncatedBPTT
                and x.ndim == 3 and x.shape[1] > self.conf.tbptt_fwd_length):
            tlen = int(self.conf.tbptt_fwd_length)
            carries = self._zero_carries(x.shape[0])
            total = None    # loss accumulates ON DEVICE: the old
            nseg = 0        # per-segment float() blocked every segment
            _g = _guardian.ACTIVE
            with _mon.span("train.dispatch"):
                for t0 in range(0, x.shape[1], tlen):
                    xs = x[:, t0:t0 + tlen]
                    ys = y[:, t0:t0 + tlen] if y.ndim == 3 else y
                    fs = None if fmask is None else fmask[:, t0:t0 + tlen]
                    ls = None if lmask is None else lmask[:, t0:t0 + tlen]
                    if _g is not None:
                        (self._params, self._opt_state, self._state,
                         carries, loss, gnorm, ok) = \
                            self._train_step_tbptt_guarded(
                                self._params, self._opt_state, self._state,
                                carries, xs, ys, fs, ls,
                                jax.random.fold_in(sub, t0),
                                _g.lr_scale, _g.max_gnorm)
                        # retryable=False: the batch's earlier healthy
                        # segments already updated params
                        _g.on_step(loss, gnorm, ok, retryable=False)
                    else:
                        (self._params, self._opt_state, self._state,
                         carries, loss) = self._train_step_tbptt(
                            self._params, self._opt_state, self._state,
                            carries, xs, ys, fs, ls,
                            jax.random.fold_in(sub, t0))
                    total = loss if total is None else total + loss
                    nseg += 1
            self._score = None if total is None else total / nseg
        else:
            _g = _guardian.ACTIVE
            with _mon.span("train.dispatch"):
                if _g is not None:
                    (self._params, self._opt_state, self._state, loss,
                     gnorm, ok) = self._train_step_guarded(
                        self._params, self._opt_state, self._state, x, y,
                        fmask, lmask, sub, _g.lr_scale, _g.max_gnorm)
                else:
                    self._params, self._opt_state, self._state, loss = \
                        self._train_step(
                            self._params, self._opt_state, self._state,
                            x, y, fmask, lmask, sub)
                self._score = loss    # device scalar; score() floats it
            if _g is not None:
                # device scalars only — the guardian materializes them
                # in one stacked read at its check cadence
                _g.on_step(loss, gnorm, ok)
        self._iteration += 1
        # most recent training batch, for listeners that inspect
        # activations (StatsListener histograms — ≡ the reference
        # dashboard's activation charts over the last minibatch);
        # _params_version counts REAL updates (the scanned path fires k
        # listener calls per single update)
        self._last_features = x
        self._params_version = getattr(self, "_params_version", 0) + 1
        with _mon.span("train.listeners"):
            for listener in self._listeners:
                listener.iterationDone(self, self._iteration, self._epoch)
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_end()

    # -- layerwise unsupervised pretraining (≡ MultiLayerNetwork.pretrain
    # / pretrainLayer: VAE ELBO, historically RBM contrastive divergence) -
    def pretrainLayer(self, layer_idx, data, epochs=1):
        """Unsupervised-train one layer (must define pretrain_loss) on the
        activations feeding it; one jitted step over that layer's params."""
        layer = self.layers[int(layer_idx)]
        if not hasattr(layer, "pretrain_loss"):
            return self  # ≡ reference: non-pretrainable layers are skipped
        key = str(layer_idx)
        tx = build_optimizer(
            layer.updater or self.conf.defaults.get("updater"),
            self.conf.defaults.get("gradientNormalization"),
            self.conf.defaults.get("gradientNormalizationThreshold", 1.0),
            self.conf.defaults.get("weightDecay", 0.0) or 0.0)
        opt_state = tx.init(self._params[key])

        @jax.jit
        def multilayer_pretrain_step(p, opt, x, rng):
            loss, grads = jax.value_and_grad(layer.pretrain_loss)(p, x, rng)
            updates, opt = tx.update(grads, opt, p)
            return optax.apply_updates(p, updates), opt, loss

        def batches():
            if hasattr(data, "reset"):
                data.reset()
                for ds in data:
                    yield as_jax(ds.features)
            else:
                yield as_jax(data.features if isinstance(data, DataSet)
                             else data)

        p = self._params[key]
        for _ in range(int(epochs)):
            for feats in batches():
                if layer_idx > 0:
                    feats = self.activateSelectedLayers(
                        0, layer_idx - 1, feats).jax()
                pp = self.conf.preprocessors.get(int(layer_idx))
                if pp is not None:
                    feats = pp.preProcess(feats)
                self._rng_key, sub = jax.random.split(self._rng_key)
                p, opt_state, loss = multilayer_pretrain_step(
                    p, opt_state, feats, sub)
                self._score = loss    # lazy; score() floats on demand
        self._params[key] = p
        self._build_optimizer()  # opt state shapes unchanged but refresh
        return self

    def pretrain(self, data, epochs=1):
        """≡ reference pretrain(iterator): layerwise over all layers that
        support unsupervised pretraining."""
        for i in range(len(self.layers)):
            self.pretrainLayer(i, data, epochs)
        return self

    @with_crash_dump
    def fit(self, data, labels=None, epochs=None, stepsPerDispatch=1,
            prefetch=None):
        """stepsPerDispatch > 1 (iterator form only): group consecutive
        same-shape batches and run each group as ONE lax.scan dispatch —
        numerically identical to the sequential loop (tested), but pays
        the host→device round-trip once per group instead of per batch.
        Groups flush early on a shape change, so ragged tails stay exact.
        TBPTT configs ignore it (the segment loop owns the dispatch).

        `.gradientAccumulation(G)` on the conf (iterator form): every G
        consecutive same-shape batches become ONE accumulated optimizer
        step in one dispatch (scan sums grads, single update) — the
        G×-effective-batch path; takes precedence over stepsPerDispatch
        and composes with an installed guardian (one verdict per real
        update). Sub-G remainders run as ordinary per-batch steps.

        prefetch (iterator form, async-supporting iterators): staging
        queue depth for the background device-staging prefetcher — batch
        N+1 is pulled, preprocessed, and copied into XLA-owned device
        buffers while step N computes. Default
        runtime.pipeline.DEFAULT_PREFETCH (2); 0 disables. Combined with
        the lazy score (no per-step float(loss)) a listener-free fit
        performs ZERO host-blocking syncs — see README 'Host pipeline &
        async dispatch'."""
        if self._params is None:
            self.init()
        if labels is not None:  # fit(features, labels)
            try:
                with _mon.span("fit"):
                    self._fit_batch(as_jax(data), as_jax(labels))
            finally:           # retire even on a raise: a FAILED fit is
                #                not a wedged one (see iterator path)
                if _watchdog.ACTIVE is not None:
                    _watchdog.ACTIVE.retire(f"multilayer@{id(self):x}")
            return self
        if isinstance(data, DataSet):
            try:
                with _mon.span("fit"):
                    self._fit_batch(data.features, data.labels,
                                    data.labelsMask, data.featuresMask)
            finally:
                if _watchdog.ACTIVE is not None:
                    _watchdog.ACTIVE.retire(f"multilayer@{id(self):x}")
            return self
        # iterator
        from deeplearning4j_tpu.nn.conf.builders import BackpropType
        accum = int(self.conf.defaults.get("gradientAccumulation", 1)
                    or 1)
        k = max(1, int(stepsPerDispatch))
        if self.conf.backprop_type == BackpropType.TruncatedBPTT:
            k, accum = 1, 1   # the segment loop owns the dispatch
        if accum > 1:
            # accumulation groups G batches into ONE optimizer step —
            # it owns the grouping; stepsPerDispatch (k separate
            # updates per dispatch) does not compose with it
            k = accum
        elif _guardian.ACTIVE is not None:
            k = 1    # guardian needs per-step health verdicts; a scan
            #          group would hide k-1 of them inside one dispatch
            #          (an ACCUMULATED group is one update with one
            #          verdict, so accum > 1 stays on)
        n_epochs = int(epochs) if epochs is not None else 1

        def flush(group):
            if len(group) == k and accum > 1:
                self._fit_batches_accum(group)
            elif len(group) == k:
                self._fit_batches_scanned(group)
            else:        # sub-k remainder: avoid a fresh per-length trace
                for f, l, lm, fm in group:
                    self._fit_batch(f, l, lm, fm)

        it, _pf = _pipeline.maybe_prefetch(data, prefetch)
        try:
            for _ in range(n_epochs):
                with _mon.span("fit.epoch"):
                    if hasattr(it, "reset"):
                        it.reset()
                    group, group_sig = [], None
                    for ds in _mon.traced_iter(it):
                        if _faults.ACTIVE is not None:
                            _faults.ACTIVE.fire(_faults.DATA_NEXT)
                        if k == 1:
                            self._fit_batch(ds.features, ds.labels,
                                            ds.labelsMask, ds.featuresMask)
                            continue
                        sig = self._batch_sig(ds)
                        if group and (sig != group_sig or len(group) >= k):
                            flush(group)
                            group = []
                        group_sig = sig
                        group.append((ds.features, ds.labels,
                                      ds.labelsMask, ds.featuresMask))
                    if group:
                        flush(group)
                    self._epoch += 1
                    with _mon.span("fit.epoch_listeners"):
                        for listener in self._listeners:
                            if hasattr(listener, "onEpochEnd"):
                                listener.onEpochEnd(self)
        finally:
            # the fit ended (or raised): this trainer's heartbeat is no
            # longer stall evidence — an armed watchdog must not age it
            # into a false trip while other trainers keep running
            if _watchdog.ACTIVE is not None:
                _watchdog.ACTIVE.retire(f"multilayer@{id(self):x}")
            if _pf is not None:
                _pf.close()
        return self

    # -- evaluation -------------------------------------------------------
    def evaluate(self, iterator, prefetch=None):
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        e = Evaluation()
        self._eval_loop(iterator, e, prefetch=prefetch)
        return e

    def evaluateROC(self, iterator, threshold_steps=0, prefetch=None):
        from deeplearning4j_tpu.eval.evaluation import ROC
        roc = ROC(threshold_steps)
        self._eval_loop(iterator, roc, prefetch=prefetch)
        return roc

    def evaluateRegression(self, iterator, prefetch=None):
        from deeplearning4j_tpu.eval.evaluation import RegressionEvaluation
        e = RegressionEvaluation()
        self._eval_loop(iterator, e, prefetch=prefetch)
        return e

    def evaluateROCMultiClass(self, iterator, threshold_steps=0,
                              prefetch=None):
        from deeplearning4j_tpu.eval.evaluation import ROCMultiClass
        roc = ROCMultiClass(threshold_steps)
        self._eval_loop(iterator, roc, prefetch=prefetch)
        return roc

    def evaluateCalibration(self, iterator, reliabilityDiagNumBins=10,
                            histogramNumBins=10, prefetch=None):
        """≡ MultiLayerNetwork.evaluateCalibration → EvaluationCalibration."""
        from deeplearning4j_tpu.eval.evaluation import EvaluationCalibration
        e = EvaluationCalibration(reliabilityDiagNumBins, histogramNumBins)
        self._eval_loop(iterator, e, prefetch=prefetch)
        return e

    def _eval_loop(self, iterator, evaluator, prefetch=None):
        # eval overlaps too: a background stage pulls + device-stages
        # batch N+1's features while batch N's forward pass runs
        # (labels stay host-side — the evaluator reads them there);
        # prefetch=0 forces fully synchronous eval (mirrors fit())
        it, _pf = _pipeline.maybe_prefetch(
            iterator, prefetch, stage=_pipeline.stage_for_eval)
        try:
            if hasattr(it, "reset"):
                it.reset()
            for ds in _mon.traced_iter(it, "eval.data_next"):
                if _faults.ACTIVE is not None:
                    _faults.ACTIVE.fire(_faults.EVAL_FORWARD)
                with _mon.span("eval.batch"):
                    out = self.output(ds.features, fmask=ds.featuresMask)
                    evaluator.eval(ds.labels, out.numpy(),
                                   mask=ds.labelsMask)
        finally:
            if _pf is not None:
                _pf.close()

    # -- listeners --------------------------------------------------------
    def setListeners(self, *listeners):
        if len(listeners) == 1 and isinstance(listeners[0], (list, tuple)):
            listeners = listeners[0]
        self._listeners = list(listeners)
        return self

    def addListeners(self, *listeners):
        self._listeners.extend(listeners)
        return self

    def getListeners(self):
        return list(self._listeners)

    # -- misc parity ------------------------------------------------------
    def getnLayers(self):
        return len(self.layers)

    def getLayer(self, idx):
        return self.layers[idx]

    def getEpochCount(self):
        return self._epoch

    def getIterationCount(self):
        return self._iteration

    def summary(self):
        lines = ["=" * 72,
                 f"{'Idx':<4}{'Layer':<28}{'Out':<22}{'nParams':>10}", "-" * 72]
        total = 0
        for i, l in enumerate(self.layers):
            p = self._params.get(str(i), {}) if self._params else {}
            n = sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(p))
            total += n
            out = self.conf.input_types[i]
            out_str = str(l.output_type(out).shape()) if out is not None else "?"
            lines.append(f"{i:<4}{type(l).__name__:<28}{out_str:<22}{n:>10,}")
        lines += ["-" * 72, f"Total params: {total:,}", "=" * 72]
        return "\n".join(lines)

    def clone(self):
        import copy
        m = MultiLayerNetwork(self.conf)
        if self._params is not None:
            # materialize real copies: the live net's jitted train step
            # DONATES its param buffers, which would delete shared arrays
            m._params = jax.tree_util.tree_map(jnp.copy, self._params)
            m._state = jax.tree_util.tree_map(jnp.copy, self._state)
            m._build_optimizer()
        return m

    def save(self, path, saveUpdater=True):
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer
        ModelSerializer.writeModel(self, path, saveUpdater)

    @staticmethod
    def load(path, loadUpdater=True):
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer
        return ModelSerializer.restoreMultiLayerNetwork(path, loadUpdater)
