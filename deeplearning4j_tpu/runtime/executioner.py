"""OpExecutioner facade (≡ nd4j NativeOpExecutioner / CudaExecutioner).

The reference routes every op through an executioner that picks kernels and
manages streams. Under XLA the executioner's real job collapses into: (a)
the jit dispatch cache (trace once per shape signature), (b) profiling
hooks. This facade exposes both with the reference's vocabulary, so code
written against `Nd4j.getExecutioner()` has a direct counterpart.
"""
from __future__ import annotations

import collections
import time

import jax

from deeplearning4j_tpu import monitoring as _mon


class OpExecutioner:
    _instance = None

    def __init__(self):
        self._jit_cache = {}
        self.profiling = False
        self.op_counts = collections.Counter()
        self.op_times = collections.defaultdict(float)
        # (registry, generation, dispatches, misses, compile_hist)
        self._mon_handles = None
        # cross-process warm compiles: place jax's persistent
        # compilation cache by the one rule of util/hostkey.py and
        # bridge its hit/miss events onto
        # dl4j.jit.persistent_{hits,misses} — every dl4j.jit.cache_miss
        # then splits into "paid a live XLA compile" vs "deserialized
        # from the persistent tier" (runtime/executables.py)
        from deeplearning4j_tpu.runtime.executables import \
            configure_persistent_cache
        configure_persistent_cache()

    @classmethod
    def getInstance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    # -- dispatch --------------------------------------------------------
    def exec(self, fn, *args, static_argnums=(), **kwargs):
        """Execute fn under jit with executioner-level caching/profiling.

        With monitoring enabled, cache misses also feed the global
        MetricsRegistry: `dl4j.jit.cache_misses` (counter) and
        `dl4j.jit.compile_seconds` (histogram over the wall time of the
        miss dispatch — trace + XLA compile + first run, blocked to
        completion so the number is honest). The disabled path is the
        exact pre-monitoring fast path: dict hit, call, return."""
        key = (fn, static_argnums)
        jitted = self._jit_cache.get(key)
        miss = jitted is None
        if miss:
            jitted = jax.jit(fn, static_argnums=static_argnums)
            self._jit_cache[key] = jitted
        mon_on = _mon.enabled()
        if not (self.profiling or mon_on):
            return jitted(*args, **kwargs)
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        if self.profiling or miss:
            jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if self.profiling:
            name = getattr(fn, "__name__", str(fn))
            self.op_counts[name] += 1
            self.op_times[name] += dt
        if mon_on:
            # cache the registry handles (per-dispatch _get would pay a
            # lock + key build on the hottest path), but re-resolve when
            # the registry instance or its generation changed — after
            # clear() the old Counter objects are orphans that would
            # silently drop these series from /metrics
            reg = _mon.get_registry()
            h = self._mon_handles
            if h is None or h[0] is not reg or h[1] != reg.generation:
                h = self._mon_handles = (
                    reg, reg.generation,
                    reg.counter(_mon.OP_DISPATCHES),
                    reg.counter(_mon.JIT_CACHE_MISSES),
                    reg.histogram(_mon.JIT_COMPILE_SECONDS))
            h[2].inc()
            if miss:
                h[3].inc()
                h[4].observe(dt)
                # the flight recorder attributes compile stalls to the
                # step they landed in (monitoring/steps.py)
                _mon.step_recorder().on_compile(dt)
        return out

    def commit(self):
        """≡ flushing the op queue: wait for all device work."""
        for d in jax.devices():
            try:
                jax.device_put(0.0, d).block_until_ready()
            except Exception:
                pass

    # -- profiling (≡ OpProfiler) ---------------------------------------
    def setProfilingMode(self, enabled):
        self.profiling = bool(enabled)

    def getProfilingStats(self):
        return {name: {"count": self.op_counts[name],
                       "total_time_s": self.op_times[name]}
                for name in self.op_counts}

    def printEnvironmentInformation(self):
        info = {
            "backend": jax.default_backend(),
            "devices": [str(d) for d in jax.devices()],
            "jit_cache_entries": len(self._jit_cache),
        }
        for k, v in info.items():
            print(f"{k}: {v}")
        return info
