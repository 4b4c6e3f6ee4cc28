"""Device mesh helpers (the TPU-native replacement for the reference's
device-affinity machinery in ParallelWrapper / Aeron transport config).

Axis-name conventions used across the framework:
  dp — data parallel        tp — tensor (model) parallel
  pp — pipeline parallel    sp — sequence/context parallel
  ep — expert parallel

Collectives ride ICI within a host's chips and DCN across hosts; XLA
chooses — we only annotate shardings (scaling-book recipe: pick a mesh,
annotate, let the compiler insert collectives).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, mesh=None, in_specs=None, out_specs=None, check_vma=None,
              **kw):
    """`jax.shard_map` with `check_vma` left at jax's default unless
    given. Every call site in this repo (and the tests) routes through
    here."""
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


class DeviceMesh:
    """Thin wrapper: build a named jax Mesh from the available devices.

    DeviceMesh(dp=2, tp=2, sp=2) → 8-device mesh with those axes.
    Any axis set to -1 absorbs the remaining devices.
    """

    def __init__(self, devices=None, **axes):
        devices = list(devices if devices is not None else jax.devices())
        if not axes:
            axes = {"dp": len(devices)}
        names = list(axes.keys())
        sizes = [int(v) for v in axes.values()]
        if -1 in sizes:
            known = int(np.prod([s for s in sizes if s != -1]))
            sizes[sizes.index(-1)] = len(devices) // known
        total = int(np.prod(sizes))
        if total > len(devices):
            raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} "
                             f"devices, have {len(devices)}")
        arr = np.array(devices[:total]).reshape(sizes)
        self.mesh = Mesh(arr, tuple(names))
        self.axis_names = tuple(names)
        self.shape = dict(zip(names, sizes))

    def __enter__(self):
        return self.mesh.__enter__()

    def __exit__(self, *a):
        return self.mesh.__exit__(*a)

    def sharding(self, *spec):
        """NamedSharding from axis names; None entries replicate."""
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self):
        return NamedSharding(self.mesh, P())

    def shard_batch(self, tree, axis="dp"):
        """Place arrays with dim-0 sharded over `axis`."""
        sh = self.sharding(axis)
        return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), tree)

    def replicate(self, tree):
        sh = self.replicated()
        return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), tree)

    @property
    def size(self):
        return int(np.prod(list(self.shape.values())))

    def axis_size(self, name):
        return self.shape[name]


def initialize_distributed(coordinator_address=None, num_processes=None,
                           process_id=None, **kw):
    """Multi-host bring-up (≡ SharedTrainingMaster's cluster bootstrap, but
    over jax.distributed instead of Aeron UDP). Gated: single-process
    environments (no coordinator configured anywhere) skip silently.

    Delegates to the HARDENED bootstrap in `parallel/multihost.py`:
    env-driven config (`DL4J_COORDINATOR` / `DL4J_NUM_PROCESSES` /
    `DL4J_PROCESS_ID`), connect retry/backoff under a deadline, CPU
    gloo collectives, and a post-init cross-process sanity barrier —
    failures raise typed `DistributedInitError`, never hang."""
    from deeplearning4j_tpu.parallel.multihost import initialize
    return initialize(coordinator_address, num_processes, process_id,
                      **kw)
