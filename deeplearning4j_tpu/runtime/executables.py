"""Serving-grade AOT executable store + bucket ladder + staging ring.

Without it every novel input shape pays a live `jax.jit` trace and
compile on the request path. This module removes host compiles (and
host-owned input aliasing) from serving entirely — the JAX analog of
pre-captured CUDA graphs (PAPERS.md "Hybrid JIT-CUDA Graph
Optimization"), with µ-cuDNN-style micro-batching (fixed shape buckets,
split oversized work) so the executable set is closed and finite.

Three pieces:

- **`ExecutableStore`** — per-model two-tier cache of ahead-of-time
  compiled forward executables (`jax.jit(...).lower().compile()`), one
  per bucketed input signature. Tier 0 is an in-process dict (the
  steady-state hot path: one dict get, zero locks). Tier 1 is a
  versioned on-disk cache of serialized executables
  (`jax.experimental.serialize_executable`, pickled with their arg
  treedefs) keyed by (model fingerprint, bucket signature, dtype,
  device flavour): a restarted replica `warmup()`s from disk in
  seconds — deserialize, no XLA compile. Entries that fail to load
  (corrupt, version/backend mismatch) fall back to a live compile and
  are rewritten; they NEVER crash serving. JAX's persistent
  compilation cache (placed by `util.hostkey.enable_compile_cache`,
  wired via `configure_persistent_cache()`) backs live compiles as a
  third tier, shared with training jit misses.

- **`BucketLadder`** — the closed shape vocabulary: a sorted tuple of
  batch buckets (and, for sequence models, length buckets). Requests
  pad up to the smallest admitting bucket (with a validity mask);
  oversized batches SPLIT across max-bucket chunks instead of
  compiling a new shape, so the executable set stays finite.

- **`StagingRing`** — bounded ring of pre-staged device input buffers.
  Every host batch enters the device through `xla_owned_copy`
  (runtime/pipeline.py): the executable's donated input argument is
  always XLA-owned, never a zero-copy alias of numpy memory (the PR 2
  donation hazard), so dispatch can donate inputs with zero
  host-owned aliasing.

Observability (`dl4j.exec.*` / `dl4j.jit.persistent_*`, all behind the
enabled-guard) + `GET /executables` on the UIServer via `status()`.

Cache layout (versioned; bump LAYOUT_VERSION to invalidate):

    <DL4J_EXEC_CACHE>/v3/<device-flavour>/<model-fingerprint>/<sig>.exe

- device-flavour: backend + device_kind (+ host CPU feature hash on
  CPU — XLA:CPU serializes machine code; a foreign host must MISS,
  not SIGILL: util/hostkey.py);
- model-fingerprint: conf JSON + param/state shape-dtype trees + jax
  version, so a retrained SAME architecture reuses its executables but
  any structural change misses;
- <sig>.exe: pickled {"meta": ..., "devices": [ids], "blob": (payload,
  in_tree, out_tree)}; meta re-checked at load, mismatch → treated as
  corrupt. `devices` are the ids of the devices the executable was
  compiled for: it is loaded back onto exactly those, and an entry
  whose devices this host does not have is a miss.
"""
from __future__ import annotations

import contextlib
import hashlib
import os
import pickle
import threading
import time
import warnings
import weakref

import numpy as np

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import monitoring as _mon
from deeplearning4j_tpu.resilience import faults as _faults
from deeplearning4j_tpu.runtime.pipeline import xla_owned_copy

__all__ = [
    "BucketLadder", "ExecutableStore", "FunctionStore", "StagingRing",
    "configure_persistent_cache", "forward_fn", "model_fingerprint",
    "persistent_cache_stats", "status",
]

#: bump to invalidate every on-disk serialized executable at once
#: (v3: programs carry their registered names — `jit_superstep`, not
#: `jit_run` — and an entry written before would load under the old one)
LAYOUT_VERSION = "v3"
#: on-disk serialized-executable cache root ("" → in-process tiers only)
ENV_CACHE_DIR = "DL4J_EXEC_CACHE"

_STORES = weakref.WeakSet()   # live stores, aggregated by status()


# -- persistent compilation cache (third tier) -----------------------------
_pcache_lock = threading.Lock()
_pcache_configured = False
#: process-lifetime persistent-compile-cache tallies (plain ints so the
#: split is observable even with monitoring disabled). CAVEAT on
#: "misses": jax emits its cache_misses event only when it WRITES a new
#: entry — a compile under jax_persistent_cache_min_compile_time_secs /
#: min_entry_size is neither persisted nor counted. `requests` (every
#: compile that consulted the cache) is the honest denominator:
#: non-hits = requests - hits.
_pcache_counts = {"hits": 0, "misses": 0, "requests": 0}


def _on_jax_cache_event(name, **kw):
    """Bridge jax's compilation-cache monitoring events onto dl4j
    metrics: every XLA compile request either hit the persistent cache
    (cross-process warm) or paid a live compile (hit rate =
    persistent_hits / persistent_requests)."""
    if name == "/jax/compilation_cache/cache_hits":
        _pcache_counts["hits"] += 1
        which, help_ = _mon.JIT_PERSISTENT_HITS, \
            "persistent compilation cache hits (XLA compile skipped)"
    elif name == "/jax/compilation_cache/cache_misses":
        _pcache_counts["misses"] += 1
        which, help_ = _mon.JIT_PERSISTENT_MISSES, \
            "persistent-cache misses that wrote a NEW entry (compiles " \
            "under the min-compile-time/size thresholds are not " \
            "persisted and not counted here — see persistent_requests)"
    elif name == "/jax/compilation_cache/compile_requests_use_cache":
        _pcache_counts["requests"] += 1
        which, help_ = _mon.JIT_PERSISTENT_REQUESTS, \
            "XLA compile requests that consulted the persistent cache " \
            "(hits + live compiles)"
    else:
        return
    if _mon.enabled():
        _mon.get_registry().counter(which, help=help_).inc()


def configure_persistent_cache():
    """Idempotently wire jax's persistent compilation cache.

    The directory follows the one rule of
    `util.hostkey.enable_compile_cache` ($JAX_COMPILATION_CACHE_DIR
    where set, else the checkout's `.jax_cache/`). Registers the
    cache-event listener so `dl4j.jit.persistent_{hits,misses}` count
    the first-tier vs persistent-tier split for EVERY jit in the process
    (training included). Returns the effective cache dir."""
    global _pcache_configured
    with _pcache_lock:
        if not _pcache_configured:
            jax.monitoring.register_event_listener(_on_jax_cache_event)
            from deeplearning4j_tpu.util.hostkey import enable_compile_cache
            enable_compile_cache()
            _pcache_configured = True
        return jax.config.jax_compilation_cache_dir


def persistent_cache_stats():
    """{'hits': n, 'misses': n} for this process (monitoring-free)."""
    return dict(_pcache_counts)


# -- identity --------------------------------------------------------------
def device_flavour():
    """Short key for "an executable compiled here runs there". XLA:CPU
    serializes host machine code — key by CPU feature flags + jax build
    (util/hostkey.py) so a foreign host misses instead of SIGILLing;
    accelerators key by backend + device_kind + jax version."""
    backend = jax.default_backend()
    kind = jax.devices()[0].device_kind.replace(" ", "_")
    if backend == "cpu":
        from deeplearning4j_tpu.util.hostkey import host_cpu_key
        return f"cpu-{host_cpu_key()}"
    return f"{backend}-{kind}-jax{jax.__version__}"


def _shape_dtype_tree(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),
            tuple((tuple(l.shape), str(jnp.result_type(l)))
                  for l in leaves))


def model_fingerprint(model):
    """Identity of the model's TRACE: configuration + parameter/state
    structure (+ compute dtype). Parameter VALUES are executable
    arguments, so a retrained model reuses its cached executables;
    any conf or shape change produces a different fingerprint."""
    try:
        conf_s = model.conf.toJson()
    except Exception:  # noqa: BLE001 — conf not JSON-able: repr identity
        conf_s = repr(getattr(model, "conf", type(model).__name__))
    parts = (type(model).__name__, conf_s,
             str(getattr(model, "_compute_dtype", "float32")),
             _shape_dtype_tree(getattr(model, "_params", {})),
             _shape_dtype_tree(getattr(model, "_state", {})))
    return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]


def forward_fn(model, with_mask=False):
    """Pure inference forward `(params, state, *xs[, mask]) -> (y, ...)`
    suitable for AOT lowering — same trace the jitted train step uses,
    minus loss/grad. `with_mask` appends a (B, T) validity mask input
    (length-bucketed sequence serving). Returns a TUPLE of outputs."""
    is_graph = hasattr(model, "outputSingle")   # ComputationGraph
    if is_graph:
        input_names = list(model.conf.input_names)
        output_names = list(model.conf.output_names)

        def fwd(params, state, *args):
            mask = args[len(input_names)] if with_mask else None
            ins = dict(zip(input_names, args))
            fmasks = ({n: mask for n in input_names} if with_mask
                      else None)
            acts, _, _ = model._forward(params, state, ins, False, None,
                                        fmasks)
            return tuple(acts[n] for n in output_names)
    else:
        def fwd(params, state, *args):
            mask = args[1] if with_mask else None
            y, _, _, _ = model._forward(params, state, args[0], False,
                                        None, mask=mask)
            return (y,)
    return fwd


# -- bucket ladder ---------------------------------------------------------
class BucketLadder:
    """The serving shape vocabulary: batch buckets + optional sequence
    length buckets. `bucket(n)` → smallest batch bucket admitting n
    rows (None: oversized, split via `chunks(n)`); `length_bucket(t)`
    → smallest length bucket ≥ t. A sequence LONGER than the top rung
    serves at its native length (one extra cached executable — size
    the top rung to the longest supported input); the batch axis can
    split across dispatches, the time axis cannot."""

    def __init__(self, batch=(1, 2, 4, 8, 16, 32), length=None):
        self.batch = tuple(sorted({int(b) for b in batch}))
        if not self.batch or self.batch[0] < 1:
            raise ValueError(f"batch buckets must be >= 1: {batch}")
        self.length = (None if length is None
                       else tuple(sorted({int(t) for t in length})))
        if self.length is not None and self.length[0] < 1:
            raise ValueError(f"length buckets must be >= 1: {length}")

    @property
    def max_batch(self):
        return self.batch[-1]

    def bucket(self, n):
        for b in self.batch:
            if n <= b:
                return b
        return None

    def chunks(self, n):
        """Row counts of the dispatches serving an n-row batch: greedy
        max-bucket chunks + one bucketed remainder (µ-cuDNN's
        micro-batch split — never a novel shape)."""
        out = []
        while n > self.max_batch:
            out.append(self.max_batch)
            n -= self.max_batch
        if n:
            out.append(n)
        return out

    def length_bucket(self, t):
        if self.length is None:
            return t
        for b in self.length:
            if t <= b:
                return b
        return t   # over-long: native length, never truncate

    def __repr__(self):
        return f"BucketLadder(batch={self.batch}, length={self.length})"


# -- the stores ------------------------------------------------------------
class _Entry:
    __slots__ = ("call", "source", "cost")

    def __init__(self, call, source):
        self.call = call            # compiled/loaded executable
        self.source = source        # "compile" | "disk"
        self.cost = None            # {"flops","bytes_accessed"} or None


def _cost_of(call):
    """XLA's static cost analysis for one compiled executable:
    {"flops", "bytes_accessed"} floats, or None when the backend
    doesn't expose it. Pure host metadata — no dispatch, no sync."""
    try:
        ca = call.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0))
        bytes_accessed = float(ca.get("bytes accessed", 0.0))
        if flops <= 0.0 and bytes_accessed <= 0.0:
            return None
        return {"flops": flops, "bytes_accessed": bytes_accessed}
    except Exception:  # noqa: BLE001 — cost is advisory, never fatal
        return None


class _AotStoreBase:
    """Shared two-tier AOT executable machinery: tier 0 in-process dict
    (the hot path: one dict get, no locks), tier 1 versioned on-disk
    serialized executables under the shared cache layout. Subclasses
    supply WHAT gets lowered (a model forward, a named decode
    function); this base owns identity, the memory→disk→compile
    resolution flow, persistence, and stats."""

    kind = "aot"

    def __init__(self, fingerprint, directory=None):
        self.directory = (os.environ.get(ENV_CACHE_DIR) or None
                          if directory is None else (directory or None))
        self.fingerprint = fingerprint
        self.flavour = device_flavour()
        self.trace_calls = 0        # times a python fn was traced
        # `load_seconds` / `compile_seconds` sum the miss path's two
        # ways out (always on: they cost the hot path nothing), so
        # "time to resume from the on-disk store" is a number
        self.stats = {"memory_hits": 0, "disk_hits": 0, "compiles": 0,
                      "deserialize_failures": 0, "serialize_failures": 0,
                      "load_seconds": 0.0, "compile_seconds": 0.0}
        self._mem = {}
        self._lock = threading.Lock()
        # third tier: live compiles (cache-layout misses) still warm
        # the cross-process persistent compilation cache
        configure_persistent_cache()
        _STORES.add(self)

    def _counted(self, fwd, name):
        """`fwd` with its traces counted, under `name`: jax names the
        compiled module after the function (`jit_<name>`), which is how
        a profiler trace tells the store's programs apart."""
        def run(*args):
            self.trace_calls += 1   # once per TRACE, never per call
            return fwd(*args)
        run.__name__ = run.__qualname__ = name
        return run

    # -- hot path ---------------------------------------------------------
    def lookup(self, key):
        """Steady state: one dict get, no locks, no jax."""
        e = self._mem.get(key)
        if e is None:
            return None
        self.stats["memory_hits"] += 1
        return e

    # -- miss path (boundary: the lint stops descending here) -------------
    def _entry_path(self, key, sig=None):
        """The on-disk entry of `key`; `sig`, where given, is the
        lowered argument signature, hashed with it, so that a program
        whose arguments changed under the same key is a clean miss."""
        ident = key if sig is None else (key, sig)
        h = hashlib.sha256(repr(ident).encode()).hexdigest()[:24]
        return os.path.join(self.directory, LAYOUT_VERSION, self.flavour,
                            self.fingerprint, h + ".exe")

    def _meta(self):
        return {"layout": LAYOUT_VERSION, "jax": jax.__version__,
                "backend": jax.default_backend(), "flavour": self.flavour,
                "fingerprint": self.fingerprint}

    def _count(self, name, help_):
        if _mon.enabled():
            _mon.get_registry().counter(name, help=help_).inc()

    def _note_cost(self, key, e):
        """Record the executable's static cost once, at compile/load
        time (miss path only — the steady-state lookup never re-reads
        it): per-entry on the store status, and per-signature gauges so
        tokens/s has a FLOPs-per-dispatch denominator."""
        e.cost = _cost_of(e.call)
        if e.cost is not None and _mon.enabled():
            reg = _mon.get_registry()
            labels = {"store": self.kind, "signature": repr(key)[:120]}
            reg.gauge(_mon.EXEC_FLOPS, labels=labels,
                      help="XLA cost-analysis FLOPs per dispatch of "
                           "this cached executable").set(e.cost["flops"])
            reg.gauge(_mon.EXEC_BYTES_ACCESSED, labels=labels,
                      help="XLA cost-analysis bytes accessed per "
                           "dispatch of this cached executable") \
               .set(e.cost["bytes_accessed"])

    def _resolve(self, key, lower_fn, sig=None):
        """Memory → disk (deserialize, no XLA compile) → live compile
        (persisted back), under the store lock. Corrupt or mismatched
        disk entries count `deserialize_failures` and fall through to
        the live compile — never crash, never go stale. `sig` names the
        disk entry together with `key` (`_entry_path`)."""
        with self._lock:
            e = self._mem.get(key)
            if e is not None:
                self.stats["memory_hits"] += 1
                return e
            # chaos site: a fault here simulates a corrupt/unreachable
            # executable cache on the miss path (warmup or a novel
            # signature) — never the in-memory steady state above
            if _faults.ACTIVE is not None:
                _faults.ACTIVE.fire(_faults.EXECUTABLES_LOAD)
            path = (self._entry_path(key, sig) if self.directory else None)
            if path is not None and os.path.exists(path):
                with self._miss("load", key):
                    e = self._load_disk(key, path)
                if e is not None:
                    self._mem[key] = e
                    self._note_cost(key, e)
                    return e
            with self._miss("compile", key):
                e = self._compile_live(key, lower_fn, path)
            self._mem[key] = e
            self._note_cost(key, e)
            return e

    @contextlib.contextmanager
    def _miss(self, what, key):
        """One way out of a miss, `load` or `compile`: a span
        `exec.<what>` with the store key, and its seconds summed into
        `stats["<what>_seconds"]`."""
        t0 = time.perf_counter()
        try:
            with _mon.span("exec." + what, key=repr(key)):
                yield
        finally:
            self.stats[what + "_seconds"] += time.perf_counter() - t0

    def _load_disk(self, key, path):
        try:
            with open(path, "rb") as f:
                rec = pickle.load(f)
            if rec.get("meta") != self._meta():
                raise ValueError(f"cache meta mismatch: {rec.get('meta')}")
            local = {d.id: d for d in jax.local_devices()}
            if not all(i in local for i in rec["devices"]):
                # compiled for devices this host does not have: a miss,
                # not a corrupt entry (another host may still load it)
                return None
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = rec["blob"]
            call = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[local[i] for i in rec["devices"]])
            self.stats["disk_hits"] += 1
            self._count(_mon.EXEC_DISK_HITS,
                        "serving executables deserialized from the "
                        "on-disk AOT cache (no XLA compile)")
            return _Entry(call, "disk")
        except Exception:  # noqa: BLE001 — any bad entry → live compile
            self.stats["deserialize_failures"] += 1
            self._count(_mon.EXEC_DESERIALIZE_FAILURES,
                        "corrupt/mismatched AOT cache entries (fell "
                        "back to live compile)")
            try:
                os.remove(path)
            except OSError:
                pass
            return None

    def _compile_live(self, key, lower_fn, path):
        t0 = time.perf_counter()
        pcache_hits = _pcache_counts["hits"]
        compiled = lower_fn().compile()
        dt = time.perf_counter() - t0
        # served from jax's persistent cache, not compiled (another
        # thread's hit in the same instant only costs a recompile)
        from_pcache = _pcache_counts["hits"] > pcache_hits
        self.stats["compiles"] += 1
        if _mon.enabled():
            reg = _mon.get_registry()
            reg.counter(_mon.EXEC_COMPILES,
                        help="live serving-executable compiles (cold "
                             "cache or novel signature)").inc()
            reg.histogram(_mon.EXEC_COMPILE_SECONDS,
                          help="wall time of live serving compiles") \
               .observe(dt)
        e = _Entry(compiled, "compile")
        if path is None:
            return e
        # A compile served from jax's persistent kernel cache
        # serializes an INCOMPLETE payload on XLA:CPU (the object code
        # is not re-embedded). Some such payloads fail at reload
        # ("Symbols not found" — the round-trip check in _persist
        # catches those in-process); on the installed XLA others load
        # and fail only when RUN ("Function ... not found"), which no
        # load-time check sees — so on the CPU backend a cache-served
        # executable is never persisted at all. (On the TPU the same
        # round trip was run end to end: chip_smoke.py's warm restart
        # loads and serves entries serialized from cache hits.)
        if from_pcache and jax.default_backend() == "cpu":
            verdict = "broken"
        else:
            verdict = self._persist(key, path, compiled)
        if verdict == "broken":
            # Force ONE fresh compile outside that cache and persist
            # it, so a restarted replica really does warm from disk
            # with zero compiles instead of silently degrading. Only
            # the broken-payload signature retries: a backend that
            # cannot serialize at all (or a failing write) keeps the
            # old count-and-move-on behavior — recompiling would buy
            # nothing there.
            fresh = self._compile_uncached(lower_fn)
            if fresh is not None \
                    and self._persist(key, path, fresh) is True:
                e = _Entry(fresh, "compile")
        return e

    @staticmethod
    def _compile_uncached(lower_fn):
        """Really recompile, bypassing BOTH jax compile caches.
        Two latches have to be broken: the in-memory compilation LRU
        would hand back the very same symbol-less executable without
        compiling at all (jax.clear_caches()), and jax latches its
        is-persistent-cache-used verdict process-globally, so the
        enable_compilation_cache(False) scope only takes effect after
        a reset_cache(); reset again afterwards so the next unrelated
        compile re-evaluates back to enabled. Cost: a process-wide
        jit-cache flush — acceptable on this path, which only runs at
        store warmup when a broken payload was already detected (later
        retraces recompile against the still-warm kernel cache)."""
        try:
            from jax._src import compilation_cache as _cc
            from jax._src.config import enable_compilation_cache
            try:
                with enable_compilation_cache(False):
                    _cc.reset_cache()
                    jax.clear_caches()
                    return lower_fn().compile()
            finally:
                _cc.reset_cache()
        except Exception:  # noqa: BLE001 — keep the cached compile
            return None

    def _persist(self, key, path, compiled):
        """Serialize + verify + write one entry. Returns True when the
        entry was written, "broken" when serialization produced an
        UNLOADABLE payload (the deserialize_and_load round-trip failed
        — the kernel-cache incomplete-payload signature, worth a fresh
        recompile), or False when the backend cannot serialize / the
        write failed (nothing a recompile would change). An unloadable
        payload is never written to disk."""
        try:
            from jax.experimental import serialize_executable as _se
            blob = _se.serialize(compiled)
        except Exception:  # noqa: BLE001 — backend may not serialize
            self._count_serialize_failure()
            return False
        # the devices this executable was compiled for: a reload must
        # run on exactly these (jax's default is every local device,
        # which breaks a one-device executable on a multi-device host
        # at its first call)
        devices = compiled.runtime_executable().local_devices()
        try:
            # round-trip check: deserialization failures surface HERE,
            # at persist time, not as a mystery on the next replica
            _se.deserialize_and_load(*blob, execution_devices=devices)
        except Exception:  # noqa: BLE001 — incomplete payload
            self._count_serialize_failure()
            return "broken"
        try:
            rec = {"meta": self._meta(), "key": key, "blob": blob,
                   "devices": [d.id for d in devices]}
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                pickle.dump(rec, f)
            os.replace(tmp, path)   # atomic: readers see whole files
            return True
        except Exception:  # noqa: BLE001 — unwritable cache dir
            self._count_serialize_failure()
            return False

    def _count_serialize_failure(self):
        self.stats["serialize_failures"] += 1
        self._count(_mon.EXEC_SERIALIZE_FAILURES,
                    "serving executables that could not be "
                    "serialized to disk (in-process cache only)")

    @staticmethod
    def _entry_status(k, e):
        d = {"signature": repr(k), "source": e.source}
        if e.cost is not None:
            d["flops"] = e.cost["flops"]
            d["bytes_accessed"] = e.cost["bytes_accessed"]
            d["cost"] = ("%.3g MFLOPs / %.3g MB per dispatch"
                         % (e.cost["flops"] / 1e6,
                            e.cost["bytes_accessed"] / 1e6))
        return d

    def status(self):
        return {"kind": self.kind,
                "fingerprint": self.fingerprint,
                "flavour": self.flavour,
                "directory": self.directory,
                "entries": [self._entry_status(k, e)
                            for k, e in sorted(self._mem.items(),
                                               key=lambda kv: repr(kv[0]))],
                "trace_calls": self.trace_calls,
                **self.stats}


class ExecutableStore(_AotStoreBase):
    """Two-tier AOT executable cache for ONE model's serving forward.

    Hot path: `lookup(sig)` — a dict get. Miss path (the ONLY place a
    trace or compile may happen; scripts/check_fastpath.py enforces
    that the serving hot path never reaches past `lookup`):
    `load_or_compile(sig)` under a lock — disk tier first, live
    `jit().lower().compile()` last, serialized back to disk."""

    kind = "model-forward"

    def __init__(self, model, directory=None, donate_inputs=True):
        self.model = model
        self.donate_inputs = bool(donate_inputs)
        super().__init__(model_fingerprint(model), directory=directory)
        # masked variant: (B, T) validity mask appended after the
        # inputs (length-bucketed sequence serving pads the time axis)
        self._fwds = {
            False: self._counted(forward_fn(model, with_mask=False),
                                 "forward"),
            True: self._counted(forward_fn(model, with_mask=True),
                                "forward_masked")}

    # -- hot path ---------------------------------------------------------
    def lookup(self, sig, with_mask=False):
        """Steady state: one dict get, no locks, no jax."""
        return super().lookup((sig, with_mask))

    # -- miss path (boundary: the lint stops descending here) -------------
    def _abstract_args(self, sig, with_mask):
        sds = jax.ShapeDtypeStruct
        as_sds = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda l: sds(jnp.shape(l), jnp.result_type(l)), t)
        xs = [sds(shape, jnp.dtype(dt)) for shape, dt in sig]
        if with_mask:
            # (B, T) validity mask over the first (sequence) input
            xs.append(sds(tuple(sig[0][0][:2]), jnp.dtype("float32")))
        return (as_sds(self.model._params), as_sds(self.model._state),
                *xs)

    def _lower(self, sig, with_mask):
        """Trace + lower (no XLA compile). Inputs (incl. the mask) are
        donated so dispatch reuses the staged XLA-owned buffers."""
        args = self._abstract_args(sig, with_mask)
        donate = (tuple(range(2, len(args))) if self.donate_inputs
                  else ())
        with warnings.catch_warnings():
            # XLA:CPU ignores donation ("donated buffers were not
            # usable") — harmless here, load-bearing on TPU
            warnings.simplefilter("ignore", UserWarning)
            return jax.jit(self._fwds[with_mask],
                           donate_argnums=donate).lower(*args)

    def load_or_compile(self, sig, with_mask=False):
        """Resolve one bucketed signature through the base tiers."""
        return self._resolve((sig, with_mask),
                             lambda: self._lower(sig, with_mask))

    # -- warmup / status --------------------------------------------------
    def warmup(self, sigs):
        """Pre-resolve signatures (the bucket ladder) — each either a
        bare sig or a (sig, with_mask) pair. Disk entries deserialize;
        only truly novel signatures compile. Returns
        {compiled, from_disk, seconds}."""
        before_c = self.stats["compiles"]
        before_d = self.stats["disk_hits"]
        t0 = time.perf_counter()
        for s in sigs:
            if (isinstance(s, tuple) and len(s) == 2
                    and isinstance(s[1], bool)):
                self.load_or_compile(s[0], with_mask=s[1])
            else:
                self.load_or_compile(s)
        return {"compiled": self.stats["compiles"] - before_c,
                "from_disk": self.stats["disk_hits"] - before_d,
                "seconds": time.perf_counter() - t0}

    def status(self):
        base = super().status()
        base["model"] = type(self.model).__name__
        base["entries"] = [dict(self._entry_status(k, e),
                                signature=repr(k[0]), masked=k[1])
                           for k, e in sorted(self._mem.items(),
                                              key=lambda kv: repr(kv[0]))]
        return base


class FunctionStore(_AotStoreBase):
    """Two-tier AOT cache of NAMED functions (the generation decode
    path: step / admit / retire / grow executables, one per cache-rung
    or prompt-bucket signature).

    `register(name, fn, donate_argnums=...)` declares the traceable;
    `load_or_compile((name, ...), example_args)` lowers it against the
    example's shapes/dtypes with the declared donation and runs it
    through the same memory → disk → live-compile tiers as
    ExecutableStore (so a restarted generation replica warms from disk
    in deserialize time). The hot path is `lookup(key)` — one dict get;
    the serving/decode-loop lints hold the trace boundary here too."""

    kind = "function"

    def __init__(self, fingerprint, directory=None):
        super().__init__(fingerprint, directory=directory)
        self._fns = {}

    def register(self, name, fn, donate_argnums=()):
        self._fns[name] = (self._counted(fn, name),
                           tuple(donate_argnums))
        return self

    # -- miss path (boundary: the lint stops descending here) -------------
    def _lower_named(self, name, example_args):
        fn, donate = self._fns[name]
        sds = jax.ShapeDtypeStruct
        abstract = jax.tree_util.tree_map(
            lambda l: sds(jnp.shape(l), jnp.result_type(l)), example_args)
        with warnings.catch_warnings():
            # XLA:CPU ignores donation — harmless there, load-bearing
            # on TPU (the decode state is donated through every step)
            warnings.simplefilter("ignore", UserWarning)
            return jax.jit(fn, donate_argnums=donate).lower(*abstract)

    def load_or_compile(self, key, example_args):
        """key: (name, *static identity); example_args: concrete or
        ShapeDtypeStruct positional args the executable will be called
        with. Resolves through memory → disk → live compile. The disk
        entry is named from the key AND the args' shapes and dtypes: a
        store directory kept across an upgrade that changed a program's
        arguments hands the new server no stale program."""
        name = key[0]
        if name not in self._fns:
            raise KeyError(f"no function registered under {name!r}")
        args = tuple(example_args)
        return self._resolve(key, lambda: self._lower_named(name, args),
                             _shape_dtype_tree(args))


def status():
    """Aggregate cache status for every live store (GET /executables)."""
    return {"stores": [s.status() for s in list(_STORES)],
            "persistent_compile_cache": {
                "directory": jax.config.jax_compilation_cache_dir,
                **persistent_cache_stats()}}


# -- pre-staged device input ring ------------------------------------------
class StagingRing:
    """Bounded ring of pre-staged device input buffers.

    Every buffer is produced by `xla_owned_copy` — an XLA-owned copy,
    never a zero-copy alias of numpy memory — so the dispatch may
    DONATE it (the executable reuses the input allocation for outputs)
    with zero host-owned aliasing: the exact hazard class PR 2
    root-caused (donated alias → free() of numpy-owned memory).

    `stage()` RETURNS the staged buffers to the caller — each thread
    dispatches exactly what it staged, so concurrent dispatchers (a
    degraded multi-waiter fallback, shutdown's drain racing a live
    collector) can never serve each other's inputs. The ring only
    bounds how many staged batches may be in flight at once; the
    caller `release()`s its slot once dispatch has consumed (donated)
    the buffers."""

    def __init__(self, depth=2):
        self.depth = max(1, int(depth))
        self._lock = threading.Lock()
        self._free = threading.Semaphore(self.depth)
        self._in_flight = 0
        self.staged = 0     # lifetime stages

    def stage(self, host_arrays, block=True):
        """Copy host (numpy) arrays into fresh XLA-owned device buffers
        and return them. Blocks while `depth` batches are already in
        flight (dispatch is behind) unless block=False (then None)."""
        if not self._free.acquire(blocking=block):
            return None
        bufs = tuple(xla_owned_copy(np.asarray(a)) for a in host_arrays)
        with self._lock:
            self._in_flight += 1
            occupancy = self._in_flight
            self.staged += 1
        if _mon.enabled():
            reg = _mon.get_registry()
            reg.counter(_mon.SERVING_STAGED_BUFFERS,
                        help="input batches staged into XLA-owned "
                             "device buffers").inc()
            reg.gauge(_mon.SERVING_STAGING_OCCUPANCY,
                      help="staged-but-undispatched ring slots") \
               .set(occupancy)
        return bufs

    def release(self):
        """Free one slot — the staged buffers were dispatched (and
        donated: the executable owns their memory now)."""
        with self._lock:
            if self._in_flight == 0:
                return          # tolerate unmatched release
            self._in_flight -= 1
            occupancy = self._in_flight
        self._free.release()
        if _mon.enabled():
            _mon.get_registry().gauge(
                _mon.SERVING_STAGING_OCCUPANCY,
                help="staged-but-undispatched ring slots") \
                .set(occupancy)

    def __len__(self):
        with self._lock:
            return self._in_flight
