"""Test harness: force an 8-device virtual CPU mesh (SURVEY.md §4).

Must set env vars BEFORE jax initializes its backend, hence module level in
conftest. Multi-chip sharding paths (parallel/) run against these virtual
devices; the real TPU is only used by chip_smoke.py and bench.py, and by
the few tests that run on a chip only (each skips elsewhere), under
`DL4J_TEST_ON_CHIP=1`, which leaves the platform alone.
"""
import os

if os.environ.get("DL4J_TEST_ON_CHIP") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Tests check numerics against numpy oracles: use full-precision matmuls
# (production code keeps the platform default — bf16 MXU passes on TPU).
jax.config.update("jax_default_matmul_precision", "highest")

# Persistent compilation cache (grad-of-conv compiles cost ~30 s each on
# one core; caching makes test reruns compile-free), placed by the repo's
# one rule: $JAX_COMPILATION_CACHE_DIR where set, else this checkout's
# .jax_cache/, keyed by host CPU features — XLA:CPU stores AOT machine
# code and a cache from a different machine type risks SIGILL.
# 2.0 s floor, NOT lower: a borderline ~1 s compile (the zero1
# accumulated-bucketed step) produces a serialized executable that
# deserializes WRONG on this XLA:CPU build — readers get bad numerics
# (test_zero1_rides_the_accumulated_bucketed_step fails) and a corrupt
# heap that segfaults the GC, while the writing run stays green on its
# in-memory executable. Sub-2 s compiles are cheap to redo; caching
# them only plants landmines (see util/hostkey.enable_compile_cache).
from deeplearning4j_tpu.util.hostkey import enable_compile_cache  # noqa: E402

enable_compile_cache(min_compile_secs=2.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

# Preload orbax BEFORE any test compiles: its lazy import drags in the whole
# google-cloud/aiohttp stack mid-suite (first ElasticCheckpointer
# construction) — a multi-second import churn that lands while live jaxlib
# MLIR objects are being garbage-collected and makes any latent heap
# corruption (see the cache note above) crash right there instead of at
# exit. Importing it here, while no MLIR objects exist yet, keeps module
# state deterministic and removes the mid-suite pause. If the suite ever
# starts failing deterministically with wrong numerics + GC segfaults,
# suspect a poisoned .jax_cache entry first — diagnosis recipe in
# .claude/skills/verify/SKILL.md.
import orbax.checkpoint  # noqa: E402, F401

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


# -- test tiers (round-3 VERDICT weak 6/8: suite wall-time) -----------------
# Two mechanisms:
#   pytest -m smoke                       → curated fast core subset (<120 s
#                                           warm on the 1-vCPU box)
#   DL4J_TPU_TEST_TIER=smoke pytest ...   → everything MINUS the slowest,
#                                           compile-heavy modules
# Default (no marker, no env) runs the full suite — the human default.
_SLOW_MODULES = {"test_multihost.py", "test_zoo.py", "test_kernels.py",
                 "test_keras_import.py", "test_elastic_images.py",
                 "test_pretrained.py", "test_recurrent.py", "test_rl.py",
                 "test_rl_conv.py"}

#: curated `-m smoke` subset: one fast module per core subsystem (ops,
#: network classes, losses, eval, data, serde) — a CI-style signal that
#: stays inside any driver window
_SMOKE_MODULES = {"test_ops.py", "test_multilayer.py", "test_eval.py",
                  "test_losses_tail.py", "test_datasets.py",
                  "test_serialization.py", "test_clustering.py",
                  "test_graph_embeddings.py",
                  "test_image_transforms.py", "test_resilience.py"}


def pytest_collection_modifyitems(config, items):
    for item in items:
        # minutes-long scale checks and slow soaks never belong in the
        # smoke signal
        if item.fspath.basename in _SMOKE_MODULES \
                and "memory_bounded" not in item.name \
                and item.get_closest_marker("slow") is None:
            item.add_marker(pytest.mark.smoke)
    if os.environ.get("DL4J_TPU_TEST_TIER", "full").lower() != "smoke":
        return
    skip = pytest.mark.skip(reason="smoke tier (DL4J_TPU_TEST_TIER=smoke)")
    for item in items:
        if item.fspath.basename in _SLOW_MODULES:
            item.add_marker(skip)
