"""Driver `fit`: a network trained through the public `fit()` on a pool of
seeded batches that the benchmark's own iterator cycles until the clock
ends the epoch. `data: device` hands `fit()` device-resident batches (the
host does nothing per step); `data: host` hands it float32 numpy batches,
so that `fit()`'s own prefetch stages them, as a user's iterator would."""
from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.harness import trace

#: the window opens at a device sync after this many steps of the measured
#: fit(): with `data: host` the first two are served from the prefetch queue
OPEN_AT = 3


def _iterator(batches, batch_size, more, host):
    from deeplearning4j_tpu.datasets.iterators import DataSetIterator

    class Pool(DataSetIterator):
        """Cycles `batches` for as long as `more()` says."""

        def __init__(self):
            super().__init__(batch_size)
            self.handed = 0

        def hasNext(self):
            return more(self.handed)

        def next(self, num=None):
            with trace.annotate("iterator.next"):
                self._check_has_next()
                ds = batches[self.handed % len(batches)]
                self.handed += 1
                return ds

        def reset(self):        # fit() resets at the start of an epoch:
            pass                # the pool just goes on cycling

        def asyncSupported(self):
            return host

        def numExamples(self):
            return 1 << 62

    return Pool()


class _Clock:
    """TrainingListener: stamps every step on the host's clock and never
    reads the score, except at the steps named in `read_at` (1-based within
    this fit), where it syncs with the device and keeps the loss."""

    def __init__(self, read_at=(), tracer=None, trace_after=None):
        self.read_at = set(read_at)
        self.tracer, self.trace_after = tracer, trace_after
        self.stamps, self.losses, self.synced_at = [], {}, {}
        self._trace_until = None

    def iterationDone(self, model, iteration, epoch):
        n = len(self.stamps) + 1
        if n in self.read_at:
            self.losses[n] = float(model.score())
            self.synced_at[n] = time.perf_counter()
        now = time.perf_counter()
        self.stamps.append(now)
        if self.tracer is not None and OPEN_AT in self.synced_at:
            if self._trace_until is None:
                if now >= self.synced_at[OPEN_AT] + self.trace_after:
                    self.tracer.start(inside="fit")
                    self._trace_until = (time.perf_counter()
                                         + trace.TRACE_SECONDS)
            elif now >= self._trace_until:
                self.tracer.stop()
                self.tracer = None


class Driver:
    def __init__(self, built, workload, seed, cache_dir, on_chip):
        self.built, self.workload = built, workload
        self.notes = []
        self.context = {}

    # -- set-up ------------------------------------------------------------
    def setup(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.runtime.pipeline import StagedBatch

        self.host = {"device": False, "host": True}[self.workload["data"]]
        pool = self.built.make_pool(int(self.workload["pool_batches"]))
        if self.host:
            self.batches = [DataSet(np.asarray(x), np.asarray(y))
                            for x, y in pool]
        else:
            # the program's own device-resident stand-in for a DataSet
            # (DataSet itself converts what it is given to numpy)
            self.batches = [StagedBatch(x, y) for x, y in pool]

    def warm(self):
        """One fit() of `warmup_steps` on the pool's first batch repeated:
        compiles (or loads) the step, and gives the losses `check` reads."""
        from deeplearning4j_tpu.runtime import executables

        steps = int(self.workload["warmup_steps"])
        before = executables.persistent_cache_stats()
        clock = _Clock(read_at=(1, steps))
        net = self.built.net
        net.setListeners(clock)
        net.fit(_iterator(self.batches[:1], self.built.batch,
                          lambda handed: handed < steps, self.host))
        after = executables.persistent_cache_stats()
        self.warm_losses = (clock.losses[1], clock.losses[steps])
        w = {k: after[k] - before[k] for k in after}
        w["compiled"] = w["misses"]     # entries the cache did not hold
        self.context["warm"] = w
        return w

    def check(self):
        """Every loss finite; the first where random data puts it; the last
        of the warm-up, on a repeated batch, below the first."""
        first, last = self.warm_losses
        lo, hi = self.built.first_loss_range()
        self.notes.append(f"warm-up losses {first:.4f} -> {last:.4f} "
                          f"(first expected in [{lo:.2f}, {hi:.2f}])")
        return bool(math.isfinite(first) and math.isfinite(last)
                    and lo <= first <= hi and last < first)

    # -- the measured run --------------------------------------------------
    def measure(self, seconds, tracer=None):
        clock = _Clock(read_at=(OPEN_AT,), tracer=tracer,
                       trace_after=trace.trace_after(seconds))

        def more(handed):
            opened = clock.synced_at.get(OPEN_AT)
            return opened is None or time.perf_counter() < opened + seconds

        net = self.built.net
        net.setListeners(clock)
        with trace.annotate("fit"):
            net.fit(_iterator(self.batches, self.built.batch, more,
                              self.host))
        self.final_loss = float(net.score())        # closes the window
        t_close = time.perf_counter()
        if clock.tracer is not None and clock._trace_until is not None:
            clock.tracer.stop()                     # fit ended inside it
        t_open = clock.synced_at[OPEN_AT]
        self.opened_wall = time.time() - (time.perf_counter() - t_open)
        self.steps = len(clock.stamps) - OPEN_AT
        window = t_close - t_open
        self.context["window_s"] = window
        self.context["host_ahead_s"] = t_close - clock.stamps[-1]
        self.context["batch"] = self.built.batch
        self.notes.append(
            f"{self.steps} steps of batch {self.built.batch} in a window of "
            f"{window:.3f} s; the host handed out its last batch "
            f"{self.context['host_ahead_s']:.3f} s before the device "
            f"finished; loss at the close {self.final_loss:.4f}")
        return {"train_samples_per_s":
                self.steps * self.built.batch / window}

    def counts(self):
        """(correct, attempted, failed) in steps. The loss at the close has
        only to be finite: what hundreds of steps on random labels do to it
        is training dynamics, not a fault of the program."""
        return bool(math.isfinite(self.final_loss)), self.steps, 0
