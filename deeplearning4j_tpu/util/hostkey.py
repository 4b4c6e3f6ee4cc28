"""Host-keyed persistent-compile-cache paths.

XLA:CPU stores AOT machine code in the jax persistent cache; entries
written on a different machine type load with "could lead to execution
errors such as SIGILL" warnings. Keying the cache directory by the host's
CPU feature flags makes cross-machine entries simply miss instead."""
from __future__ import annotations

import hashlib
import os
import platform


def host_cpu_key() -> str:
    """Short stable hash of this host's CPU feature flags AND the jax/
    python flavour. The AOT machine-code flavour depends on the compiling
    jax build as well as the CPU (observed: two jax installs on one box
    sharing a cache produce 'prefer-no-gather ... could lead to SIGILL'
    load warnings), so both go into the key."""
    feats = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    feats += " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    try:
        from jax import version as _jv
        feats += f" jax={_jv.__version__}"
    except Exception:
        pass
    import sys
    feats += f" py={sys.version_info[:2]} exe={sys.executable}"
    return hashlib.sha256(feats.encode()).hexdigest()[:12]


def compile_cache_dir() -> str:
    """This checkout's fixed compile-cache directory: `.jax_cache/` beside
    the package, one sub-directory per host flavour. Derived from the
    package's own location and nothing that changes between runs — the
    path is part of jax's cache key, so a directory that moves never
    hits."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache", f"host-{host_cpu_key()}")


def enable_compile_cache(min_compile_secs: float = 2.0) -> str:
    """THE compile-cache rule, shared by chip_smoke.py, bench.py,
    __graft_entry__.py, tests/conftest.py and
    runtime.executables.configure_persistent_cache: where
    JAX_COMPILATION_CACHE_DIR is set, jax already uses that directory and
    none is set in code; where it is not, the cache lives in
    compile_cache_dir(). Returns the directory in effect.

    min_compile_secs floor of 2.0 is deliberate: XLA:CPU's serialized
    executable for at least one borderline-fast (~1 s) compile in this
    codebase deserializes WRONG — the reader gets bad numerics and a
    corrupted heap (GC segfault at exit) while the writer, which keeps
    using its in-memory executable, stays green. Keeping sub-2 s
    compiles out of the cache costs little (they are cheap to redo by
    definition) and keeps the poison class off disk entirely."""
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = compile_cache_dir()
        if jax.config.jax_compilation_cache_dir != directory:
            jax.config.update("jax_compilation_cache_dir", directory)
            # jax binds the cache object at first use; re-point it or a
            # cache initialized earlier keeps its old directory
            from jax.experimental.compilation_cache import \
                compilation_cache as _cc
            _cc.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return directory
