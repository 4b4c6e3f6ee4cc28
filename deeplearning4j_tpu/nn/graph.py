"""ComputationGraph (≡ deeplearning4j-nn :: graph.ComputationGraph).

DAG-structured network over GraphNode topology: multi-input, multi-output,
per-output losses summed into one scalar — so the whole training step is
still ONE jitted XLA executable (forward over topo order + backward +
updaters), the TPU-native counterpart of the reference's vertex-by-vertex
executioner dispatch."""
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
from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.nn import accum as _accum
from deeplearning4j_tpu.nn.multilayer import (_apply_layer, _hook_params,
                                              _l1l2_penalty)
from deeplearning4j_tpu.nn.updaters import build_optimizer, same_updater
from deeplearning4j_tpu.ops.ndarray import NDArray, as_jax, resolve_dtype


class ComputationGraph:
    def __init__(self, conf):
        self.conf = conf
        self.nodes = conf.nodes
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
        self._fused_pairs = {}   # bn node -> conv node (nn/fused.py)
        self._fused_convs = set()

    # layer-bearing node names in topo order
    @property
    def _layer_names(self):
        return [n for n in self.conf.topo_order
                if self.nodes[n].kind == "layer"]

    @property
    def _output_layers(self):
        return [self.nodes[n].ref for n in self.conf.output_names]

    # -- lifecycle -------------------------------------------------------
    def init(self):
        if not self.conf.node_output_types:
            raise ValueError("setInputTypes(...) required before init()")
        from deeplearning4j_tpu.nn.fused import (find_conv1x1_bn_fusions,
                                                 fusion_enabled)
        # per-instance execution decision; the shared conf is never mutated
        self._fused_pairs = (find_conv1x1_bn_fusions(self.conf)
                             if fusion_enabled() else {})
        self._fused_convs = set(self._fused_pairs.values())
        key = jax.random.PRNGKey(self.conf.seed)
        ps, ss = {}, {}
        for name in self.conf.topo_order:
            node = self.nodes[name]
            if node.kind == "vertex" and hasattr(node.ref, "initialize"):
                # parameterized vertex (AttentionVertex): params thread
                # through the same jitted step as layer params
                key, sub = jax.random.split(key)
                p, s = node.ref.initialize(sub, *node.resolved_input_types)
                if p:
                    ps[name] = p
                if s:
                    ss[name] = s
                continue
            if node.kind != "layer":
                continue
            key, sub = jax.random.split(key)
            p, s, _ = node.ref.initialize(sub, node.resolved_input_type)
            if p:
                ps[name] = p
            if s:
                ss[name] = s
        self._params = ps
        self._state = ss
        self._build_optimizer()
        return self

    def _build_optimizer(self):
        defaults = self.conf.defaults
        global_updater = defaults.get("updater")
        overrides = {n: self.nodes[n].ref.updater for n in self._layer_names
                     if self.nodes[n].ref.updater is not None
                     and not same_updater(self.nodes[n].ref.updater,
                                          global_updater)}
        gn = defaults.get("gradientNormalization")
        gn_thr = defaults.get("gradientNormalizationThreshold", 1.0)
        wd = defaults.get("weightDecay", 0.0) or 0.0
        if not overrides:
            self._tx = build_optimizer(global_updater, gn, gn_thr, wd)
        else:
            transforms = {"__global__": build_optimizer(global_updater, gn, gn_thr, wd)}
            transforms.update({k: build_optimizer(u, gn, gn_thr, wd)
                               for k, u in overrides.items()})
            labels = {k: (k if k in overrides else "__global__")
                      for k in self._params}
            self._tx = optax.multi_transform(transforms, labels)
        self._opt_state = self._tx.init(self._params)

    def clone(self):
        m = ComputationGraph(self.conf)
        m._fused_pairs = dict(self._fused_pairs)
        m._fused_convs = set(self._fused_convs)
        if self._params is not None:
            # real copies — the live net's jitted train step donates buffers
            m._params = jax.tree_util.tree_map(jnp.copy, self._params)
            m._state = jax.tree_util.tree_map(jnp.copy, self._state)
            m._build_optimizer()
        return m

    # -- parameters ------------------------------------------------------
    def numParams(self):
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(self._params))

    def params(self):
        names = sorted(self._params)
        leaves = jax.tree_util.tree_leaves({n: self._params[n] for n in names})
        if not leaves:
            return NDArray(jnp.zeros((0,)))
        return NDArray(jnp.concatenate([l.ravel() for l in leaves]))

    def paramTable(self):
        flat = {}
        for name, p in (self._params or {}).items():
            for k, v in p.items():
                flat[f"{name}_{k}"] = NDArray(v)
        return flat

    def getLayer(self, name):
        return self.nodes[name].ref

    # -- forward ---------------------------------------------------------
    def _forward(self, params, state, inputs, train, rng, fmasks=None,
                 want=None, carries=None):
        """inputs: dict name->array. Returns (acts dict, preacts dict for
        output layers, new_state[, new_carries when carries given]).

        carries: optional {node_name: carry} — recurrent layer nodes then
        run via scan_apply so hidden state threads across calls
        (≡ ComputationGraph.rnnTimeStep's stored state)."""
        if (train and carries is None
                and getattr(self.conf, "remat_policy", "none") == "blocks"
                and not getattr(self, "_fused_pairs", None)
                and (fmasks is None
                     or all(m is None for m in fmasks.values()))):
            # per-residual-block selective recompute: only block-boundary
            # activations are saved for backward, block internals re-run
            # under jax.checkpoint (ROADMAP item 3's FLOPs-for-bytes
            # trade; gradients equal the un-rematted step — tier-1)
            return self._forward_remat_blocks(params, state, inputs, rng)
        acts = {}
        preacts = {}
        new_state = dict(state)
        new_carries = {} if carries is not None else None
        mask0 = None
        if fmasks:
            mask0 = next((m for m in fmasks.values() if m is not None), None)
        node_masks = {}
        for name, x in inputs.items():
            acts[name] = x.astype(self._compute_dtype)
            node_masks[name] = (fmasks.get(name, mask0) if fmasks else None)
        li = 0
        for name in self.conf.topo_order:
            node = self.nodes[name]
            if node.kind == "input":
                continue
            parents = [acts[p] for p in node.inputs]
            parent_masks = [node_masks.get(p) for p in node.inputs]
            if node.kind == "vertex":
                pmask = next((m for m in parent_masks if m is not None), None)
                if fmasks and getattr(node.ref, "maskName", None):
                    pmask = fmasks.get(node.ref.maskName, pmask)
                if hasattr(node.ref, "initialize"):
                    acts[name] = node.ref.apply(
                        *parents, params=params.get(name, {}), mask=pmask)
                else:
                    acts[name] = node.ref.apply(*parents, mask=pmask)
                node_masks[name] = node.ref.feed_forward_mask(*parent_masks)
                continue
            layer = node.ref
            # frozen layers (transfer learning) always run inference-mode
            ltrain = train and not getattr(layer, "frozen", False)
            x = parents[0]
            pmask = parent_masks[0]
            if node.preprocessor is not None:
                x = node.preprocessor.preProcess(x)
            if name in getattr(self, "_fused_convs", ()):
                # conv half of a conv1x1+BN fused pair (nn/fused.py):
                # pass the input through; the BN node runs the fused
                # kernel with both param groups and back-fills this
                # node's true activation. li still advances so every
                # layer keeps its rng stream slot.
                acts[name] = x
                node_masks[name] = pmask
                li += 1
                continue
            lrng = jax.random.fold_in(rng, li) if rng is not None else None
            li += 1
            p = _hook_params(layer, params.get(name, {}), ltrain, lrng)
            s = state.get(name, {})
            fc = getattr(self, "_fused_pairs", {}).get(name)
            if fc is not None:
                from deeplearning4j_tpu.nn.fused import fused_apply
                y, ns, y_conv = fused_apply(self.nodes[fc].ref, layer,
                                            params.get(fc, {}), p, s, x,
                                            ltrain)
                acts[name] = y
                acts[fc] = y_conv  # feedForward sees the real conv output
                if ns:
                    new_state[name] = ns
                node_masks[name] = pmask
                continue
            if name in self.conf.output_names and hasattr(layer, "compute_loss"):
                xd = layer._dropout_in(x, ltrain, lrng)
                if getattr(layer, "pre_activation_takes_mask", False):
                    pre = layer.pre_activation(p, xd, mask=pmask)
                else:
                    pre = layer.pre_activation(p, xd)
                preacts[name] = pre
                from deeplearning4j_tpu.nn.activations import get_activation
                acts[name] = get_activation(layer.activation)(pre)
                node_masks[name] = pmask
            elif carries is not None and getattr(layer, "is_recurrent",
                                                 False):
                if not hasattr(layer, "scan_apply"):
                    # Bidirectional/MaskZeroLayer etc. have no single
                    # forward carry — silently stateless results would be
                    # wrong (the reference throws here too)
                    raise ValueError(
                        f"rnnTimeStep: {type(layer).__name__} '{name}' "
                        "cannot run step-by-step (no carried state "
                        "protocol); use output() on whole sequences")
                x = layer._dropout_in(x, ltrain, lrng)
                y, carry = layer.scan_apply(p, x, carries.get(name), pmask)
                acts[name] = y
                new_carries[name] = carry
                node_masks[name] = (layer.feed_forward_mask(pmask)
                                    if pmask is not None else None)
            else:
                y, ns = _apply_layer(layer, p, s, x, ltrain, lrng, pmask)
                acts[name] = y
                if ns:
                    new_state[name] = ns
                node_masks[name] = (layer.feed_forward_mask(pmask)
                                    if pmask is not None else None)
        if carries is not None:
            return acts, preacts, new_state, new_carries
        return acts, preacts, new_state

    # -- per-block selective recompute (rematPolicy "blocks") ------------
    @functools.cached_property
    def _remat_plan(self):
        """(plan, rng_index): conf.remat_plan() — segments plus their
        ACTUALLY-SAVED outputs (shared with the traffic ledger) — and
        the layer→rng-stream index map (the SAME fold_in(rng, i)
        stream the plain path uses, so dropout/weight-noise draws are
        identical with remat on or off)."""
        plan = self.conf.remat_plan()
        rng_index = {}
        li = 0
        for name in self.conf.topo_order:
            if self.nodes[name].kind == "layer":
                rng_index[name] = li
                li += 1
        return plan, rng_index

    def _run_node_plain(self, name, params, state, acts, new_state,
                        preacts, rng, rng_index, train=True):
        """One node of the mask-free forward (block-remat segments and
        the quantized-graph executor run nodes through this — masked/
        carried/fused forwards use the general loop above). Mirrors
        that loop's per-node semantics exactly: preprocessors, frozen
        layers, param hooks, dropout-in + pre_activation for loss
        heads."""
        node = self.nodes[name]
        parents = [acts[p] for p in node.inputs]
        if node.kind == "vertex":
            if hasattr(node.ref, "initialize"):
                acts[name] = node.ref.apply(
                    *parents, params=params.get(name, {}), mask=None)
            else:
                acts[name] = node.ref.apply(*parents, mask=None)
            return
        layer = node.ref
        ltrain = train and not getattr(layer, "frozen", False)
        x = parents[0]
        if node.preprocessor is not None:
            x = node.preprocessor.preProcess(x)
        lrng = (jax.random.fold_in(rng, rng_index[name])
                if rng is not None else None)
        p = _hook_params(layer, params.get(name, {}), ltrain, lrng)
        s = state.get(name, {})
        if name in self.conf.output_names and hasattr(layer,
                                                      "compute_loss"):
            xd = layer._dropout_in(x, ltrain, lrng)
            if getattr(layer, "pre_activation_takes_mask", False):
                pre = layer.pre_activation(p, xd, mask=None)
            else:
                pre = layer.pre_activation(p, xd)
            preacts[name] = pre
            from deeplearning4j_tpu.nn.activations import get_activation
            acts[name] = get_activation(layer.activation)(pre)
        else:
            y, ns = _apply_layer(layer, p, s, x, ltrain, lrng, None)
            acts[name] = y
            if ns:
                new_state[name] = ns

    def _forward_remat_blocks(self, params, state, inputs, rng):
        """Training forward where each residual-block segment runs under
        jax.checkpoint: backward sees only the BLOCK-BOUNDARY
        activations (the fan-out tensors a residual graph must keep
        anyway) and recomputes the conv/BN internals — on an HBM-bound
        step that converts the measured ~27%-of-MFU conv FLOP headroom
        into eliminated activation reads."""
        plan, rng_index = self._remat_plan
        acts = {name: x.astype(self._compute_dtype)
                for name, x in inputs.items()}
        preacts = {}
        new_state = dict(state)
        for seg, outs in plan:
            seg_set = set(seg)
            ext = []
            for name in seg:
                for p in self.nodes[name].inputs:
                    if p not in seg_set and p not in ext:
                        ext.append(p)
            seg_params = {n: params[n] for n in seg if n in params}
            seg_state = {n: state[n] for n in seg if n in state}

            def seg_fn(sp, ss, ext_acts, key, _seg=tuple(seg),
                       _ext=tuple(ext), _outs=tuple(outs)):
                a = dict(zip(_ext, ext_acts))
                ns, pre = {}, {}
                for n in _seg:
                    self._run_node_plain(n, sp, ss, a, ns, pre, key,
                                         rng_index)
                return tuple(a[n] for n in _outs), ns, pre

            out, ns, pre = jax.checkpoint(seg_fn)(
                seg_params, seg_state,
                tuple(acts[p] for p in ext), rng)
            acts.update(zip(outs, out))
            new_state.update(ns)
            preacts.update(pre)
        return acts, preacts, new_state

    def _as_input_dict(self, inputs):
        if isinstance(inputs, dict):
            return {k: as_jax(v) for k, v in inputs.items()}
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        return {n: as_jax(v) for n, v in zip(self.conf.input_names, inputs)}

    @with_crash_dump
    def output(self, *inputs, train=False, fmasks=None):
        if len(inputs) == 1:
            inputs = inputs[0]
        ins = self._as_input_dict(inputs)
        if fmasks is not None:
            fmasks = {k: (None if v is None else as_jax(v))
                      for k, v in fmasks.items()}
        acts, _, _ = self._forward(self._params, self._state, ins, train,
                                   None, fmasks)
        outs = [NDArray(acts[n]) for n in self.conf.output_names]
        return outs[0] if len(outs) == 1 else outs

    def outputSingle(self, *inputs):
        out = self.output(*inputs)
        return out[0] if isinstance(out, list) else out

    def getOutputLayer(self, index=0):
        """≡ ComputationGraph.getOutputLayer(idx) — conf object of the
        idx-th output layer."""
        return self._output_layers[index]

    def getPredictedObjects(self, inputs, confThreshold=0.5,
                            nmsThreshold=0.4):
        """Detection convenience over a Yolo2OutputLayer output (≡
        YoloUtils.getPredictedObjects). `inputs` is one array, or a
        list/dict for multi-input graphs (NOT *args — thresholds stay
        positional like the MultiLayerNetwork twin).
        Returns List[List[DetectedObject]]."""
        out_layer = self._output_layers[0]
        if not hasattr(out_layer, "getPredictedObjects"):
            raise TypeError(
                f"output layer {type(out_layer).__name__} has no detection "
                "decode — getPredictedObjects needs a Yolo2OutputLayer head")
        y = self.outputSingle(inputs)
        return out_layer.getPredictedObjects(as_jax(y), confThreshold,
                                             nmsThreshold)

    def feedForward(self, inputs, train=False):
        ins = self._as_input_dict(inputs)
        acts, _, _ = self._forward(self._params, self._state, ins, train, None)
        return {k: NDArray(v) for k, v in acts.items()}

    # -- stateful RNN inference (≡ ComputationGraph.rnnTimeStep) ---------
    def rnnTimeStep(self, *inputs):
        if len(inputs) == 1:
            inputs = inputs[0]
        ins = self._as_input_dict(inputs)
        squeeze = any(v.ndim == 2 for v in ins.values())
        ins = {k: (v[:, None, :] if v.ndim == 2 else v)
               for k, v in ins.items()}
        if getattr(self, "_rnn_carries", None) is None:
            self._rnn_carries = {}
        acts, _, _, self._rnn_carries = self._forward(
            self._params, self._state, ins, False, None,
            carries=self._rnn_carries)
        outs = []
        for n in self.conf.output_names:
            y = acts[n]
            outs.append(NDArray(y[:, -1, :] if squeeze and y.ndim == 3
                                else y))
        return outs[0] if len(outs) == 1 else outs

    def rnnClearPreviousState(self):
        self._rnn_carries = None

    def rnnGetPreviousState(self, node_name):
        return (getattr(self, "_rnn_carries", None) or {}).get(node_name)

    # -- loss ------------------------------------------------------------
    def _loss(self, params, state, inputs, labels, fmasks, lmasks, rng,
              train=True):
        acts, preacts, new_state = self._forward(params, state, inputs, train,
                                                 rng, fmasks)
        total = 0.0
        for i, name in enumerate(self.conf.output_names):
            layer = self.nodes[name].ref
            if not hasattr(layer, "compute_loss"):
                raise ValueError(f"Output node '{name}' is not an output layer")
            y = labels[i].astype(jnp.float32)
            lm = None if lmasks is None else lmasks[i]
            if getattr(layer, "needs_features", False):
                node = self.nodes[name]
                feats = acts[node.inputs[0]]
                if node.preprocessor is not None:
                    feats = node.preprocessor.preProcess(feats)
                total = total + layer.compute_loss_with_features(
                    params.get(name, {}), y,
                    preacts[name].astype(jnp.float32),
                    feats.astype(jnp.float32), lm)
            else:
                total = total + layer.compute_loss(
                    y, preacts[name].astype(jnp.float32), lm)
        layer_list = [self.nodes[n].ref for n in self._layer_names]
        reg_params = {str(i): params.get(n, {})
                      for i, n in enumerate(self._layer_names)}
        total = total + _l1l2_penalty(layer_list, reg_params)
        return total, new_state

    def score(self, dataset=None):
        if dataset is None:
            # lazy score: _score may hold the device loss scalar; this
            # is the on-demand sync point (dl4j.pipeline.syncs)
            return _pipeline.materialize_score(self)
        ins, labels, fmasks, lmasks = self._unpack(dataset)
        # inference-mode forward (≡ reference score(DataSet) semantics)
        loss, _ = self._loss(self._params, self._state, ins, labels, fmasks,
                             lmasks, None, train=False)
        return float(loss)

    # -- training --------------------------------------------------------
    @functools.cached_property
    def _train_step(self):
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def graph_train_step(params, opt_state, state, inputs, labels, fmasks,
                             lmasks, rng):
            (loss, new_state), grads = jax.value_and_grad(
                lambda p: self._loss(p, state, inputs, labels, fmasks, lmasks,
                                     rng), has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params = self._apply_constraints(params)
            return params, opt_state, new_state, loss

        return graph_train_step

    @functools.cached_property
    def _train_step_guarded(self):
        """Guardian variant of `_train_step` (see
        MultiLayerNetwork._train_step_guarded): same update + device
        health verdict, update applied only when loss and global grad
        norm are finite and the norm is under the guardian's threshold;
        `lr_scale` implements the reduce-LR escalation rung."""
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def graph_train_step_guarded(params, opt_state, state, inputs, labels,
                                     fmasks, lmasks, rng, lr_scale, max_gnorm):
            (loss, new_state), grads = jax.value_and_grad(
                lambda p: self._loss(p, state, inputs, labels, fmasks,
                                     lmasks, rng), has_aux=True)(params)
            params, opt_state, (state,), gnorm, ok = \
                _guardian.guarded_apply(
                    tx, grads, loss, params, opt_state, lr_scale,
                    max_gnorm, constraints=self._apply_constraints,
                    extra=((new_state, state),))
            return params, opt_state, state, loss, gnorm, ok

        return graph_train_step_guarded

    def _apply_constraints(self, params):
        """Post-update constraints per layer vertex (≡ BaseConstraint)."""
        pairs = [(n, self.nodes[n].ref) for n in self._layer_names]
        if not any(getattr(l, "constraints", None) for _, l in pairs):
            return params
        from deeplearning4j_tpu.nn.constraints import apply_layer_constraints
        return apply_layer_constraints(pairs, params)

    def _pack_single(self, x, y, fmask=None, lmask=None):
        """THE single-input/single-output packing convention — the one
        place that maps flat (x, y, masks) onto this graph's kwargs
        (also used by ParallelWrapper's dp step)."""
        ins = {self.conf.input_names[0]: x}
        labels = [y]
        fmasks = None if fmask is None \
            else {self.conf.input_names[0]: fmask}
        lmasks = None if lmask is None else [lmask]
        return ins, labels, fmasks, lmasks

    def _unpack(self, ds):
        if isinstance(ds, (MultiDataSet, _pipeline.StagedMultiBatch)):
            ins = {n: jnp.asarray(f) for n, f in
                   zip(self.conf.input_names, ds.features)}
            labels = [jnp.asarray(l) for l in ds.labels]
            fmasks = None
            if ds.featuresMasks is not None:
                fmasks = {n: (None if m is None else jnp.asarray(m))
                          for n, m in zip(self.conf.input_names, ds.featuresMasks)}
            lmasks = None
            if ds.labelsMasks is not None:
                lmasks = [None if m is None else jnp.asarray(m)
                          for m in ds.labelsMasks]
            return ins, labels, fmasks, lmasks
        if isinstance(ds, (DataSet, _pipeline.StagedBatch)):
            return self._pack_single(
                jnp.asarray(ds.features), jnp.asarray(ds.labels),
                None if ds.featuresMask is None
                else jnp.asarray(ds.featuresMask),
                None if ds.labelsMask is None
                else jnp.asarray(ds.labelsMask))
        raise TypeError(f"Cannot fit on {type(ds)}")

    def _fit_batch(self, ds):
        with _mon.span("train.stage"):
            unpacked = self._unpack(ds)
        self._fit_unpacked(unpacked)

    def _fit_unpacked(self, unpacked):
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.TRAIN_DISPATCH)
        if _watchdog.ACTIVE is not None:
            _watchdog.ACTIVE.beat(f"graph@{id(self):x}")
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_start()
        ins, labels, fmasks, lmasks = unpacked
        with _mon.span("train.stage"):
            self._rng_key, sub = jax.random.split(self._rng_key)
        _g = _guardian.ACTIVE
        with _mon.span("train.dispatch"):
            if _g is not None:
                (self._params, self._opt_state, self._state, loss,
                 gnorm, ok) = self._train_step_guarded(
                    self._params, self._opt_state, self._state, ins,
                    labels, fmasks, lmasks, sub, _g.lr_scale,
                    _g.max_gnorm)
            else:
                self._params, self._opt_state, self._state, loss = \
                    self._train_step(
                        self._params, self._opt_state, self._state, ins,
                        labels, fmasks, lmasks, sub)
            self._score = loss    # device scalar; score() floats it
        if _g is not None:
            # device scalars only — materialized at the guardian's
            # check cadence, never per step
            _g.on_step(loss, gnorm, ok)
        self._iteration += 1
        self._last_features = ins     # for StatsListener histograms
        self._params_version = getattr(self, "_params_version", 0) + 1
        with _mon.span("train.listeners"):
            for listener in self._listeners:
                listener.iterationDone(self, self._iteration, self._epoch)
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_end()

    @functools.cached_property
    def _train_scan(self):
        """K graph train steps in ONE lax.scan dispatch (see
        MultiLayerNetwork._train_scan for the rationale): the scan body is
        the same update as _train_step over stacked input/label/mask
        pytrees, so k scanned steps == k sequential steps exactly."""
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def graph_train_step_scan(params, opt_state, state, ins, labels,
                                  fmasks, lmasks, rngs):
            def body(carry, inp):
                p, o, s = carry
                i_, l_, fm, lm, rng = inp
                (loss, ns), grads = jax.value_and_grad(
                    lambda pp: self._loss(pp, s, i_, l_, fm, lm, rng),
                    has_aux=True)(p)
                updates, o = tx.update(grads, o, p)
                p = optax.apply_updates(p, updates)
                p = self._apply_constraints(p)
                return (p, o, ns), loss

            (params, opt_state, state), losses = jax.lax.scan(
                body, (params, opt_state, state),
                (ins, labels, fmasks, lmasks, rngs))
            return params, opt_state, state, losses

        return graph_train_step_scan

    def _fit_batches_scanned(self, unpacked):
        """Flush a group of already-unpacked same-structure batches. Only
        full groups go through the scan — sub-k remainders run singly so
        lax.scan is traced for exactly ONE length per batch shape (each
        distinct scan length is a fresh compile)."""
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.TRAIN_DISPATCH)
        if _watchdog.ACTIVE is not None:
            _watchdog.ACTIVE.beat(f"graph@{id(self):x}")
        _ps = _prof.ACTIVE             # armed ProfileSession: the whole
        if _ps is not None:            # scanned dispatch is one "step"
            _ps.step_start()
        with _mon.span("train.stage"):
            subs = []
            for _ in unpacked:  # identical key stream to _fit_batch
                self._rng_key, sub = jax.random.split(self._rng_key)
                subs.append(sub)
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                             *unpacked)
            ins, labels, fmasks, lmasks = stacked
        with _mon.span("train.scan_dispatch"):
            (self._params, self._opt_state, self._state,
             losses) = self._train_scan(self._params, self._opt_state,
                                        self._state, ins, labels, fmasks,
                                        lmasks, jnp.stack(subs))
        self._last_features = jax.tree_util.tree_map(lambda a: a[-1], ins)
        self._params_version = getattr(self, "_params_version", 0) + 1
        with _mon.span("train.listeners"):
            if self._listeners:
                # device slices, not device_get: score() syncs only for
                # listeners that actually read it
                for i in range(len(unpacked)):
                    self._score = losses[i]
                    self._iteration += 1
                    for listener in self._listeners:
                        listener.iterationDone(self, self._iteration,
                                               self._epoch)
            else:
                self._score = losses[len(unpacked) - 1]
                self._iteration += len(unpacked)
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_end()

    # -- in-step gradient accumulation (ISSUE 14): see
    # MultiLayerNetwork._train_step_accum — G microbatches, ONE update.
    @functools.cached_property
    def _train_accum(self):
        """Accumulated graph step: `nn/accum.accum_scan` over G stacked
        batch pytrees (grads/loss summed on device, vertex state
        threaded sequentially), then ONE updater application."""
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def graph_train_step_accum(params, opt_state, state, ins, labels,
                                   fmasks, lmasks, rngs):
            grads, loss, _, state = _accum.accum_scan(
                self._accum_grad_fn, params, state,
                (ins, labels, fmasks, lmasks, rngs))
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params = self._apply_constraints(params)
            return params, opt_state, state, loss

        return graph_train_step_accum

    def _accum_grad_fn(self, params, state, inp):
        """One microbatch's ((loss, new_state), grads) for accum_scan."""
        i_, l_, fm, lm, rng = inp
        (loss, ns), grads = jax.value_and_grad(
            lambda p: self._loss(p, state, i_, l_, fm, lm, rng),
            has_aux=True)(params)
        return (loss, ns), grads

    @functools.cached_property
    def _train_accum_guarded(self):
        """Guardian variant of `_train_accum`: one verdict gates the
        accumulated update; a NaN in any microbatch poisons the
        inspected loss (see MultiLayerNetwork._train_step_accum_guarded
        for the full contract)."""
        tx = self._tx

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def graph_train_step_accum_guarded(params, opt_state, state, ins,
                                           labels, fmasks, lmasks, rngs,
                                           lr_scale, max_gnorm):
            grads, loss, micro_ok, new_state = _accum.accum_scan(
                self._accum_grad_fn, params, state,
                (ins, labels, fmasks, lmasks, rngs))
            vloss = jnp.where(micro_ok, loss, jnp.float32(jnp.nan))
            params, opt_state, (state,), gnorm, ok = \
                _guardian.guarded_apply(
                    tx, grads, vloss, params, opt_state, lr_scale,
                    max_gnorm, constraints=self._apply_constraints,
                    extra=((new_state, state),))
            return params, opt_state, state, loss, gnorm, ok

        return graph_train_step_accum_guarded

    def _fit_batches_accum(self, group):
        """Flush a FULL G-batch group of unpacked batches through one
        accumulated optimizer step (one real update: iteration count
        and listeners advance once)."""
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.TRAIN_DISPATCH)
        if _watchdog.ACTIVE is not None:
            _watchdog.ACTIVE.beat(f"graph@{id(self):x}")
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_start()
        with _mon.span("train.stage"):
            subs = []
            for _ in group:
                self._rng_key, sub = jax.random.split(self._rng_key)
                subs.append(sub)
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                             *group)
            ins, labels, fmasks, lmasks = stacked
        _g = _guardian.ACTIVE
        with _mon.span("train.accum_dispatch"):
            if _g is not None:
                (self._params, self._opt_state, self._state, loss,
                 gnorm, ok) = self._train_accum_guarded(
                    self._params, self._opt_state, self._state, ins,
                    labels, fmasks, lmasks, jnp.stack(subs),
                    _g.lr_scale, _g.max_gnorm)
            else:
                (self._params, self._opt_state, self._state,
                 loss) = self._train_accum(
                    self._params, self._opt_state, self._state, ins,
                    labels, fmasks, lmasks, jnp.stack(subs))
            self._score = loss
        if _g is not None:
            _g.on_step(loss, gnorm, ok)   # one verdict per real update
        self._iteration += 1
        self._last_features = jax.tree_util.tree_map(lambda a: a[-1], ins)
        self._params_version = getattr(self, "_params_version", 0) + 1
        with _mon.span("train.listeners"):
            for listener in self._listeners:
                listener.iterationDone(self, self._iteration, self._epoch)
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_end()

    @staticmethod
    def _batch_sig(unpacked_or_ds):
        leaves, treedef = jax.tree_util.tree_flatten(unpacked_or_ds)
        return (str(treedef), tuple(jnp.shape(x) for x in leaves))

    @with_crash_dump
    def fit(self, data, labels=None, epochs=None, stepsPerDispatch=1,
            prefetch=None):
        """stepsPerDispatch > 1 (iterator form): group consecutive
        same-structure batches into one scanned dispatch — numerically
        identical to the sequential loop (tested); ragged/odd batches
        flush the group early and run singly.

        prefetch: staging queue depth for the background device-staging
        prefetcher (async-supporting iterators; default
        runtime.pipeline.DEFAULT_PREFETCH, 0 disables) — batch N+1 is
        staged to XLA-owned device buffers while step N computes."""
        if self._params is None:
            self.init()
        if labels is not None:
            try:
                with _mon.span("fit"):
                    self._fit_batch(DataSet(as_jax(data), as_jax(labels)))
            finally:           # retire even on a raise: a FAILED fit is
                #                not a wedged one (see iterator path)
                if _watchdog.ACTIVE is not None:
                    _watchdog.ACTIVE.retire(f"graph@{id(self):x}")
            return self
        if isinstance(data, (DataSet, MultiDataSet)):
            try:
                with _mon.span("fit"):
                    self._fit_batch(data)
            finally:
                if _watchdog.ACTIVE is not None:
                    _watchdog.ACTIVE.retire(f"graph@{id(self):x}")
            return self
        accum = int(self.conf.defaults.get("gradientAccumulation", 1)
                    or 1)
        k = max(1, int(stepsPerDispatch))
        if accum > 1:
            k = accum   # accumulation owns the grouping (one update)
        elif _guardian.ACTIVE is not None:
            k = 1    # guardian needs per-step health verdicts; a scan
            #          group would hide k-1 of them inside one dispatch
            #          (an accumulated group is ONE update/verdict, so
            #          accum > 1 stays on)
        n_epochs = int(epochs) if epochs is not None else 1

        def flush(group):
            if len(group) == k and accum > 1:
                self._fit_batches_accum(group)
            elif len(group) == k:
                self._fit_batches_scanned(group)
            else:        # sub-k remainder: avoid a fresh per-length trace
                for unpacked in group:
                    self._fit_unpacked(unpacked)

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
                            self._fit_batch(ds)
                            continue
                        unpacked = self._unpack(ds)
                        sig = self._batch_sig(unpacked)
                        if group and (sig != group_sig or len(group) >= k):
                            flush(group)
                            group = []
                        group_sig = sig
                        group.append(unpacked)
                    if group:
                        flush(group)
                    self._epoch += 1
                    with _mon.span("fit.epoch_listeners"):
                        for listener in self._listeners:
                            if hasattr(listener, "onEpochEnd"):
                                listener.onEpochEnd(self)
        finally:
            # fit over: this trainer's heartbeat is no longer stall
            # evidence (see multilayer.fit)
            if _watchdog.ACTIVE is not None:
                _watchdog.ACTIVE.retire(f"graph@{id(self):x}")
            if _pf is not None:
                _pf.close()
        return self

    # -- evaluation ------------------------------------------------------
    def _eval_loop(self, iterator, evaluator, prefetch=None):
        # overlap host batch prep with the device forward pass: features
        # stage to device in the background, labels stay host-side;
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
                    out = self.output(ds.features)
                    out0 = out[0] if isinstance(out, list) else out
                    evaluator.eval(ds.labels, out0.numpy(),
                                   mask=ds.labelsMask)
        finally:
            if _pf is not None:
                _pf.close()
        return evaluator

    def evaluate(self, iterator, prefetch=None):
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        return self._eval_loop(iterator, Evaluation(), prefetch=prefetch)

    def evaluateROC(self, iterator, threshold_steps=0, prefetch=None):
        from deeplearning4j_tpu.eval.evaluation import ROC
        return self._eval_loop(iterator, ROC(threshold_steps),
                               prefetch=prefetch)

    def evaluateROCMultiClass(self, iterator, threshold_steps=0,
                              prefetch=None):
        from deeplearning4j_tpu.eval.evaluation import ROCMultiClass
        return self._eval_loop(iterator, ROCMultiClass(threshold_steps),
                               prefetch=prefetch)

    def evaluateCalibration(self, iterator, reliabilityDiagNumBins=10,
                            histogramNumBins=10, prefetch=None):
        from deeplearning4j_tpu.eval.evaluation import EvaluationCalibration
        return self._eval_loop(
            iterator, EvaluationCalibration(reliabilityDiagNumBins,
                                            histogramNumBins),
            prefetch=prefetch)

    # -- listeners / misc ------------------------------------------------
    def setListeners(self, *listeners):
        if len(listeners) == 1 and isinstance(listeners[0], (list, tuple)):
            listeners = listeners[0]
        self._listeners = list(listeners)
        return self

    def getIterationCount(self):
        return self._iteration

    def getEpochCount(self):
        return self._epoch

    def summary(self):
        lines = ["=" * 78,
                 f"{'Name':<20}{'Kind':<10}{'Inputs':<26}{'nParams':>10}",
                 "-" * 78]
        total = 0
        for name in self.conf.topo_order:
            node = self.nodes[name]
            p = (self._params or {}).get(name, {})
            n = sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(p))
            total += n
            kind = node.kind if node.kind != "layer" else type(node.ref).__name__
            lines.append(f"{name:<20}{kind:<10}{','.join(node.inputs):<26}{n:>10,}")
        lines += ["-" * 78, f"Total params: {total:,}", "=" * 78]
        return "\n".join(lines)

    def save(self, path, saveUpdater=True):
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer
        ModelSerializer.writeModel(self, path, saveUpdater)

    @staticmethod
    def load(path, loadUpdater=True):
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer
        return ModelSerializer.restoreComputationGraph(path, loadUpdater)
