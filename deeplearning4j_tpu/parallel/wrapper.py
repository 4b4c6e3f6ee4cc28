"""ParallelWrapper (≡ deeplearning4j-parallel-wrapper ::
parallelism.ParallelWrapper) — synchronous data-parallel training.

The reference clones the model per GPU, runs workers on threads, and merges
gradients through EncodedGradientsAccumulator over Aeron/NCCL. TPU-native
inversion: ONE SPMD program — parameters replicated over the `dp` mesh
axis, batch sharded on dim 0, and the gradient all-reduce is inserted by
XLA as an ICI psum inside the SAME fused step (no accumulator thread, no
encoding; see compression.py for the optional threshold-encoding parity).

Usage parity:
    pw = (ParallelWrapper.Builder(net)
          .workers(8).prefetchBuffer(4).averagingFrequency(1).build())
    pw.fit(iterator)
"""
from __future__ import annotations

import numpy as np

import jax

from deeplearning4j_tpu import monitoring as _mon
from deeplearning4j_tpu.monitoring import profiler as _prof
from deeplearning4j_tpu.parallel.mesh import DeviceMesh
from deeplearning4j_tpu.resilience import faults as _faults
from deeplearning4j_tpu.resilience import guardian as _guardian
from deeplearning4j_tpu.resilience import watchdog as _watchdog
from deeplearning4j_tpu.runtime import pipeline as _pipeline


class _StagedShards:
    """One batch already padded + dp-sharded onto the mesh by the
    prefetch worker — _fit_dataset consumes it without any host work."""

    __slots__ = ("x", "y", "fmask", "lmask")

    def __init__(self, x, y, fmask, lmask):
        self.x = x
        self.y = y
        self.fmask = fmask
        self.lmask = lmask


class ParallelWrapper:
    def __init__(self, model, workers=None, prefetch_buffer=2,
                 averaging_frequency=1, report_score=True, devices=None,
                 shard_optimizer_state=False, gradient_accumulation=None):
        self.model = model
        devs = list(devices if devices is not None else jax.devices())
        n = workers or len(devs)
        self.mesh = DeviceMesh(devs[:n], dp=n)
        self.prefetch_buffer = prefetch_buffer
        self.averaging_frequency = averaging_frequency  # sync SPMD ⇒ always 1
        self.report_score = report_score
        self.shard_optimizer_state = shard_optimizer_state  # ZeRO-1
        # G; None = inherit the model conf's gradientAccumulation —
        # an EXPLICIT 1 overrides the conf back to per-batch steps
        self.gradient_accumulation = (None if gradient_accumulation
                                      is None else
                                      int(gradient_accumulation))
        if self.gradient_accumulation is not None \
                and self.gradient_accumulation < 1:
            raise ValueError("gradient_accumulation must be >= 1")

    class Builder:
        def __init__(self, model):
            self._model = model
            self._kw = {}

        def workers(self, n):
            self._kw["workers"] = int(n)
            return self

        def prefetchBuffer(self, n):
            self._kw["prefetch_buffer"] = int(n)
            return self

        def averagingFrequency(self, n):
            self._kw["averaging_frequency"] = int(n)
            return self

        def reportScoreAfterAveraging(self, flag):
            self._kw["report_score"] = bool(flag)
            return self

        def shardOptimizerState(self, flag=True):
            """ZeRO-1: shard updater state over dp (parallel/zero.py)."""
            self._kw["shard_optimizer_state"] = bool(flag)
            return self

        def gradientAccumulation(self, n):
            """In-step microbatch accumulation: every G consecutive
            same-shape batches run as ONE dp-sharded jitted optimizer
            step (scan sums grads on device, single update) — one
            dispatch per optimizer step regardless of G, effective
            batch G× the per-dispatch footprint. Composes with the
            guardian (one verdict per real update) and takes
            precedence over stepsPerDispatch. When not set here it is
            inherited from the conf DSL's `.gradientAccumulation(G)`;
            an explicit `gradientAccumulation(1)` OVERRIDES the conf
            back to plain per-batch dp steps."""
            self._kw["gradient_accumulation"] = int(n)
            return self

        def workspaceMode(self, *_):
            return self  # XLA buffer reuse; accepted for parity

        def trainingMode(self, *_):
            return self  # always synchronous averaging (SPMD)

        def build(self):
            return ParallelWrapper(self._model, **self._kw)

    # -- device placement ------------------------------------------------
    def _shard_model(self):
        m = self.model
        m._params = self.mesh.replicate(m._params)
        if self.shard_optimizer_state:
            from deeplearning4j_tpu.parallel.zero import \
                shard_optimizer_state
            m._opt_state = shard_optimizer_state(m._opt_state, self.mesh)
        else:
            m._opt_state = self.mesh.replicate(m._opt_state)
        if m._state:
            m._state = self.mesh.replicate(m._state)

    @staticmethod
    def _pad_rows(arr, pad):
        """Append `pad` copies of the last row (row CONTENT is irrelevant —
        padded rows are zero-weighted in the loss; repeating keeps dtypes
        and value ranges valid, e.g. int label ids)."""
        return np.concatenate([arr, np.repeat(arr[-1:], pad, axis=0)])

    def _graph_model(self):
        """Resolved ONCE per wrapper: is the wrapped model a (validated)
        single-input/single-output ComputationGraph?"""
        cached = getattr(self, "_is_graph", None)
        if cached is not None:
            return cached
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        self._is_graph = isinstance(self.model, ComputationGraph)
        if self._is_graph and (len(self.model.conf.input_names) != 1
                               or len(self.model.conf.output_names) != 1):
            raise ValueError(
                "ParallelWrapper needs a single-input/single-output "
                "ComputationGraph (got "
                f"{len(self.model.conf.input_names)} inputs, "
                f"{len(self.model.conf.output_names)} outputs); use "
                "ShardedTrainer for general graphs")
        return self._is_graph

    def _host_prep(self, ds):
        """Host side of one batch: unwrap (Multi)DataSet, pad a ragged
        final batch to a dp multiple with zero-weighted rows. Returns
        numpy (feats, labs, fmask, lmask). Runs on the caller's thread
        in the synchronous path, on the prefetch worker when staging."""
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        if isinstance(ds, MultiDataSet):
            # single-array MultiDataSet (the usual graph pairing) maps
            # onto the same flat path; genuinely-multi needs ShardedTrainer
            if len(ds.features) != 1 or len(ds.labels) != 1:
                raise ValueError(
                    "ParallelWrapper.fit got a MultiDataSet with "
                    f"{len(ds.features)} feature / {len(ds.labels)} label "
                    "arrays; only single-input/single-output data is "
                    "supported — use ShardedTrainer for general graphs")
            fms = ds.featuresMasks
            lms = ds.labelsMasks
            feats = np.asarray(ds.features[0])
            labs = np.asarray(ds.labels[0])
            fm = None if not fms or fms[0] is None else np.asarray(fms[0])
            lm = None if not lms or lms[0] is None else np.asarray(lms[0])
        else:
            feats = np.asarray(ds.features)
            labs = np.asarray(ds.labels)
            lm = None if ds.labelsMask is None \
                else np.asarray(ds.labelsMask)
            fm = None if ds.featuresMask is None \
                else np.asarray(ds.featuresMask)
        pad = (-feats.shape[0]) % self.mesh.size
        if pad:
            # Ragged final batch: pad rows to a multiple of the dp
            # axis, and ZERO-WEIGHT them via the labels mask so the
            # masked-mean loss (losses._apply_mask_mean) excludes
            # them exactly — repeat-padding without a mask silently
            # biased last-batch gradients (round-1 VERDICT).
            b = feats.shape[0]
            feats = self._pad_rows(feats, pad)
            labs = self._pad_rows(labs, pad)
            if lm is None:
                mshape = labs.shape[:-1] if labs.ndim >= 2 \
                    else labs.shape
                lm = np.ones(mshape, np.float32)
            else:
                lm = self._pad_rows(lm, pad)
            lm = lm.copy()
            lm[b:] = 0.0
            if fm is not None:
                fm = self._pad_rows(fm, pad)
        if _mon.enabled():
            _mon.record_transfer(feats.nbytes + labs.nbytes
                                 + (0 if lm is None else lm.nbytes)
                                 + (0 if fm is None else fm.nbytes))
        return feats, labs, fm, lm

    def _stage(self, ds):
        """Prefetch-worker staging: host prep + dp-sharded device_put
        through XLA-owned copies (donation-safe; overlaps the NEXT
        batch's H2D transfer with the current step's compute)."""
        feats, labs, fm, lm = self._host_prep(ds)
        sh = self.mesh.sharding("dp")
        own = _pipeline.xla_owned_copy
        return _StagedShards(
            own(feats, sh), own(labs, sh),
            None if fm is None else own(fm, sh),
            None if lm is None else own(lm, sh))

    def _fit_dataset(self, ds):
        """One dp-sharded train step on a DataSet (the shared inner loop —
        also driven by EarlyStoppingParallelTrainer). Accepts either a
        raw (Multi)DataSet or a _StagedShards from the prefetcher."""
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.TRAIN_DISPATCH)
        if _watchdog.ACTIVE is not None:
            _watchdog.ACTIVE.beat(f"parallel_wrapper@{id(self):x}")
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_start()
        is_graph = self._graph_model()
        with _mon.span("train.stage"):
            if isinstance(ds, _StagedShards):
                x, y, fmask, lmask = ds.x, ds.y, ds.fmask, ds.lmask
            else:
                feats, labs, fm, lm = self._host_prep(ds)
                x = jax.device_put(feats, self.mesh.sharding("dp"))
                y = jax.device_put(labs, self.mesh.sharding("dp"))
                lmask = None if lm is None \
                    else jax.device_put(lm, self.mesh.sharding("dp"))
                fmask = None if fm is None \
                    else jax.device_put(fm, self.mesh.sharding("dp"))
            m = self.model
            m._rng_key, sub = jax.random.split(m._rng_key)
        _g = _guardian.ACTIVE
        with _mon.span("parallel.dispatch"):
            if is_graph:
                # the reference's ParallelWrapper wraps ComputationGraph
                # too; packing convention lives in
                # ComputationGraph._pack_single
                ins, labels, fmasks, lmasks = m._pack_single(x, y, fmask,
                                                             lmask)
                if _g is not None:
                    (m._params, m._opt_state, m._state, loss, gnorm,
                     ok) = m._train_step_guarded(
                        m._params, m._opt_state, m._state, ins, labels,
                        fmasks, lmasks, sub, _g.lr_scale, _g.max_gnorm)
                else:
                    m._params, m._opt_state, m._state, loss = \
                        m._train_step(m._params, m._opt_state, m._state,
                                      ins, labels, fmasks, lmasks, sub)
            else:
                ins = None
                if _g is not None:
                    (m._params, m._opt_state, m._state, loss, gnorm,
                     ok) = m._train_step_guarded(
                        m._params, m._opt_state, m._state, x, y, fmask,
                        lmask, sub, _g.lr_scale, _g.max_gnorm)
                else:
                    m._params, m._opt_state, m._state, loss = \
                        m._train_step(m._params, m._opt_state, m._state,
                                      x, y, fmask, lmask, sub)
            m._score = loss    # device scalar; score() floats on demand
        if _g is not None:
            _g.on_step(loss, gnorm, ok)   # device scalars; no sync here
        m._iteration += 1
        # StatsListener contract (ADVICE r5): the model-side fit paths set
        # both of these per real update — the wrapper's step must too, or
        # ratio/histogram collection freezes on a stale version
        m._last_features = ins if is_graph else x
        m._params_version = getattr(m, "_params_version", 0) + 1
        with _mon.span("train.listeners"):
            for listener in m._listeners:
                listener.iterationDone(m, m._iteration, m._epoch)
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_end()
        return m._score

    # -- scanned dispatch (round-5): k same-shape batches in ONE sharded
    # dispatch, reusing the model's _train_scan — the dp-path answer to
    # per-dispatch host cost.
    # Same rng key stream and math as the sequential loop: dense models
    # come out bit-identical; conv models can differ by fp-reassociation
    # noise (~1e-6) because XLA fuses the scanned conv body differently
    @staticmethod
    def _scan_sig(ds):
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        if isinstance(ds, MultiDataSet):
            return None   # multi data routes through the single path
        if ds.features is None:
            return None   # no features → non-scannable, not a TypeError
        def sh(a):
            return None if a is None else tuple(np.shape(a))
        return (sh(ds.features), sh(ds.labels), sh(ds.featuresMask),
                sh(ds.labelsMask))

    def _fit_group_scanned(self, group):
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.TRAIN_DISPATCH)
        if _watchdog.ACTIVE is not None:
            _watchdog.ACTIVE.beat(f"parallel_wrapper@{id(self):x}")
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_start()
        m = self.model
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh2 = NamedSharding(self.mesh.mesh, P(None, "dp"))  # (k, B, ...)
        def stack_put(field):
            arrs = [getattr(ds, field) for ds in group]
            if arrs[0] is None:
                return None
            stacked = np.stack([np.asarray(a) for a in arrs])
            _mon.record_transfer(stacked.nbytes)
            return jax.device_put(stacked, sh2)

        with _mon.span("train.stage"):
            subs = []
            for _ in group:   # identical key stream to the seq path
                m._rng_key, sub = jax.random.split(m._rng_key)
                subs.append(sub)
            xs, ys = stack_put("features"), stack_put("labels")
            fms, lms = stack_put("featuresMask"), stack_put("labelsMask")
        import jax.numpy as jnp
        with _mon.span("parallel.scan_dispatch"):
            if self._graph_model():
                ins, labels, fmasks, lmasks = m._pack_single(xs, ys, fms,
                                                             lms)
                (m._params, m._opt_state, m._state,
                 losses) = m._train_scan(m._params, m._opt_state, m._state,
                                         ins, labels, fmasks, lmasks,
                                         jnp.stack(subs))
                # last batch of the scanned stack, unpacked like the
                # model-side scanned path (graph.py:487)
                m._last_features = jax.tree_util.tree_map(
                    lambda a: a[-1], ins)
            else:
                (m._params, m._opt_state, m._state,
                 losses) = m._train_scan(m._params, m._opt_state, m._state,
                                         xs, ys, fms, lms, jnp.stack(subs))
                m._last_features = xs[-1]
        # ONE real param update for the whole scanned group: bump the
        # version once so StatsListener's dedup treats the k-1 inner
        # iterationDone calls as param-stale (ADVICE r5, wrapper.py:200)
        m._params_version = getattr(m, "_params_version", 0) + 1
        with _mon.span("train.listeners"):
            if m._listeners:
                for i in range(len(group)):
                    m._score = losses[i]   # device slice; lazy float
                    m._iteration += 1
                    for listener in m._listeners:
                        listener.iterationDone(m, m._iteration, m._epoch)
            else:
                m._score = losses[len(group) - 1]
                m._iteration += len(group)
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_end()

    def _fit_group_accum(self, group):
        """One ACCUMULATED dp-sharded optimizer step over G stacked
        batches — the model's `_train_accum`/`_train_step_accum` with
        input sharding (k, B, ...) = (replicated, dp): the scan sums
        per-microbatch gradients (each microbatch's psum rides the same
        program) and applies ONE update. One real update: iteration and
        listeners advance once; under a guardian the accumulated step's
        single verdict gates it (per-microbatch NaN still caught via
        the poisoned loss)."""
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.fire(_faults.TRAIN_DISPATCH)
        if _watchdog.ACTIVE is not None:
            _watchdog.ACTIVE.beat(f"parallel_wrapper@{id(self):x}")
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_start()
        m = self.model
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh2 = NamedSharding(self.mesh.mesh, P(None, "dp"))  # (G, B, ...)

        def stack_put(field):
            arrs = [getattr(ds, field) for ds in group]
            if arrs[0] is None:
                return None
            stacked = np.stack([np.asarray(a) for a in arrs])
            _mon.record_transfer(stacked.nbytes)
            return jax.device_put(stacked, sh2)

        with _mon.span("train.stage"):
            subs = []
            for _ in group:   # one key split per microbatch
                m._rng_key, sub = jax.random.split(m._rng_key)
                subs.append(sub)
            xs, ys = stack_put("features"), stack_put("labels")
            fms, lms = stack_put("featuresMask"), stack_put("labelsMask")
        import jax.numpy as jnp
        _g = _guardian.ACTIVE
        with _mon.span("parallel.accum_dispatch"):
            if self._graph_model():
                ins, labels, fmasks, lmasks = m._pack_single(xs, ys, fms,
                                                             lms)
                if _g is not None:
                    (m._params, m._opt_state, m._state, loss, gnorm,
                     ok) = m._train_accum_guarded(
                        m._params, m._opt_state, m._state, ins, labels,
                        fmasks, lmasks, jnp.stack(subs), _g.lr_scale,
                        _g.max_gnorm)
                else:
                    (m._params, m._opt_state, m._state,
                     loss) = m._train_accum(
                        m._params, m._opt_state, m._state, ins, labels,
                        fmasks, lmasks, jnp.stack(subs))
                m._last_features = jax.tree_util.tree_map(
                    lambda a: a[-1], ins)
            else:
                if _g is not None:
                    (m._params, m._opt_state, m._state, loss, gnorm,
                     ok) = m._train_step_accum_guarded(
                        m._params, m._opt_state, m._state, xs, ys, fms,
                        lms, jnp.stack(subs), _g.lr_scale, _g.max_gnorm)
                else:
                    (m._params, m._opt_state, m._state,
                     loss) = m._train_step_accum(
                        m._params, m._opt_state, m._state, xs, ys, fms,
                        lms, jnp.stack(subs))
                m._last_features = xs[-1]
            m._score = loss    # device scalar; score() floats on demand
        if _g is not None:
            _g.on_step(loss, gnorm, ok)   # one verdict per real update
        m._iteration += 1
        m._params_version = getattr(m, "_params_version", 0) + 1
        with _mon.span("train.listeners"):
            for listener in m._listeners:
                listener.iterationDone(m, m._iteration, m._epoch)
        _ps = _prof.ACTIVE
        if _ps is not None:
            _ps.step_end()

    def fit(self, iterator, epochs=1, stepsPerDispatch=1):
        """Data-parallel fit: same jitted train step as the wrapped model —
        input sharding makes it SPMD over the dp axis. stepsPerDispatch=k
        scans k same-shape batches inside ONE dispatch (ragged/odd batches
        fall back to the per-batch step; same key stream and math — dense
        models bit-identical, conv models within fp-reassociation noise).

        gradientAccumulation=G (builder knob, or inherited from the
        model conf): every G same-shape batches run as ONE accumulated
        optimizer step instead — one dispatch AND one update per group;
        takes precedence over stepsPerDispatch and stays on under a
        guardian (the accumulated step carries its own verdict)."""
        if self.model._params is None:
            self.model.init()
        self._shard_model()
        it, pf = iterator, None
        accum = self.gradient_accumulation
        if accum is None:   # unset → inherit; explicit 1 stays 1
            accum = int(self.model.conf.defaults.get(
                "gradientAccumulation", 1) or 1)
        k = max(1, int(stepsPerDispatch))
        if accum > 1:
            k = accum   # accumulation owns the grouping
        elif _guardian.ACTIVE is not None:
            k = 1    # per-step health verdicts (see model fit loops)
        if self.prefetch_buffer and hasattr(iterator, "asyncSupported") \
                and iterator.asyncSupported():
            # k == 1: stage all the way onto the mesh (pad + dp-sharded
            # device_put) in the background. k > 1: the scanned path
            # stacks host arrays per group itself, so prefetch only the
            # host pull (stage=None) and leave staging to the group.
            it = pf = _pipeline.PrefetchIterator(
                iterator, depth=self.prefetch_buffer,
                stage=self._stage if k == 1 else None)
        try:
            for _ in range(int(epochs)):
                with _mon.span("fit.epoch"):
                    if hasattr(it, "reset"):
                        it.reset()
                    if k == 1:
                        for ds in _mon.traced_iter(it):
                            self._fit_dataset(ds)
                    else:
                        group, sig = [], None

                        def flush():
                            nonlocal group
                            for g in group:   # sub-k groups run singly
                                self._fit_dataset(g)
                            group = []

                        for ds in _mon.traced_iter(it):
                            s = self._scan_sig(ds)
                            scannable = (s is not None and len(s[0]) > 0
                                         and s[0][0] % self.mesh.size == 0)
                            if not scannable:
                                flush()
                                sig = None
                                self._fit_dataset(ds)
                                continue
                            if s != sig:
                                flush()
                                sig = s
                            group.append(ds)
                            if len(group) == k:
                                if accum > 1:
                                    self._fit_group_accum(group)
                                else:
                                    self._fit_group_scanned(group)
                                group = []
                        flush()
                    self.model._epoch += 1
        finally:
            # fit over: this trainer's heartbeat is no longer stall
            # evidence (see multilayer.fit)
            if _watchdog.ACTIVE is not None:
                _watchdog.ACTIVE.retire(f"parallel_wrapper@{id(self):x}")
            if pf is not None:
                pf.close()
        return self.model

    def shutdown(self):
        pass  # no worker threads to stop: one SPMD program
