"""Byte-balanced gradient buckets for the overlapped exchange
(≡ the reference's EncodedGradientsAccumulator shipping gradient
*chunks* over Aeron as they become ready, rather than one monolithic
message after the whole backward).

PR 7's `MultiHostTrainer` all-reduced the entire gradient tree as one
logical exchange at the end of the step, so the full cross-host latency
sat exposed on the critical path. This module splits the tree into N
byte-balanced buckets; the trainer then encodes and all-reduces each
bucket as an INDEPENDENT collective, issued in program order
(encode b0 → exchange b0 → encode b1 → exchange b1 → ...), so bucket
k's collective has no data dependency on bucket k+1's encode and XLA's
latency-hiding scheduler can run them concurrently (async
all-reduce-start on TPU/GPU; verified structurally on the HLO text on
CPU, where collectives lower synchronously — see
`check_overlap_structure`).

Everything here is trace-time planning over leaf SHAPES: the plan is
computed once on the host from tree metadata (no device values touched
— lint-enforced by scripts/check_fastpath.py's training-exchange sync
rule) and then drives pure jnp concat/split inside the jitted step.

Each bucket rides ONE collective: the bucket's leaves are raveled and
concatenated into a single flat vector (same dtype per bucket — the
planner never mixes dtypes), all-reduced, then split + reshaped back.
This is also what makes the per-bucket threshold-encoder state natural:
one flat residual vector and one adaptive threshold scalar per bucket.
"""
from __future__ import annotations

import re

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["BucketPlan", "plan_buckets", "check_overlap_structure",
           "DEFAULT_NUM_BUCKETS", "ENCODE_SCOPE", "EXCHANGE_SCOPE"]

#: default bucket count when neither `num_buckets` nor `bucket_bytes`
#: is given: enough splits for the scheduler to overlap, few enough
#: that per-collective latency still amortizes
DEFAULT_NUM_BUCKETS = 4

#: named-scope stamps the trainer wraps per-bucket ops in — the HLO
#: structural check keys off these (they survive into op metadata)
ENCODE_SCOPE = "dl4j_bucket{b}_encode"
EXCHANGE_SCOPE = "dl4j_bucket{b}_exchange"


class BucketPlan:
    """Host-side plan: which flattened-tree leaf goes to which bucket.

    Attributes
    ----------
    num_buckets: int
    buckets: tuple of tuples of leaf indices (tree_flatten order inside
        each bucket — deterministic, so checkpointed per-bucket encoder
        state always lines up with the same elements).
    bucket_bytes: per-bucket payload bytes (the balance the planner
        optimized).
    """

    def __init__(self, treedef, shapes, dtypes, buckets):
        self.treedef = treedef
        self.shapes = tuple(tuple(s) for s in shapes)
        self.dtypes = tuple(np.dtype(d) for d in dtypes)
        self.buckets = tuple(tuple(b) for b in buckets)
        self.num_buckets = len(self.buckets)
        sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.leaf_sizes = tuple(sizes)
        self.bucket_elems = tuple(sum(sizes[i] for i in b)
                                  for b in self.buckets)
        self.bucket_bytes = tuple(
            sum(sizes[i] * self.dtypes[i].itemsize for i in b)
            for b in self.buckets)
        self.total_bytes = sum(self.bucket_bytes)

    def bucket_dtype(self, b):
        return self.dtypes[self.buckets[b][0]]

    # -- trace-time tensor plumbing (pure jnp; runs inside jit) ----------
    def concat(self, tree):
        """Tree -> [flat 1-D array per bucket] (ravel + concat in plan
        order). Single-leaf buckets skip the concat."""
        leaves = jax.tree_util.tree_leaves(tree)
        out = []
        for b in self.buckets:
            flats = [jnp.ravel(leaves[i]) for i in b]
            out.append(flats[0] if len(flats) == 1
                       else jnp.concatenate(flats))
        return out

    def split(self, flats):
        """[flat per bucket] -> tree (inverse of `concat`)."""
        leaves = [None] * len(self.shapes)
        for b, flat in zip(self.buckets, flats):
            off = 0
            for i in b:
                n = self.leaf_sizes[i]
                # static slice: offsets are plan constants, so XLA sees
                # plain slices (free to fuse), never dynamic-slice
                leaves[i] = flat[off:off + n].reshape(self.shapes[i])
                off += n
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def describe(self):
        """Host-side summary for telemetry / GET /health."""
        return {"num_buckets": self.num_buckets,
                "bucket_bytes": list(self.bucket_bytes),
                "total_bytes": self.total_bytes,
                "leaves": len(self.shapes)}


def plan_buckets(tree, num_buckets=None, bucket_bytes=None):
    """Byte-balanced partition of `tree`'s leaves into buckets.

    num_buckets: requested bucket count (clamped to the leaf count);
        default DEFAULT_NUM_BUCKETS.
    bucket_bytes: alternatively, a target payload per bucket — the
        planner derives the count as ceil(total/target).

    Greedy LPT (largest leaf into the lightest bucket) per dtype group:
    a bucket never mixes dtypes (its payload is ONE flat vector), so
    leaves are first grouped by dtype, each group gets buckets
    proportional to its byte share (at least one), and LPT balances
    within the group. Deterministic for a given tree structure.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("plan_buckets: empty tree")
    shapes = [tuple(getattr(l, "shape", ())) for l in leaves]
    dtypes = [np.dtype(getattr(l, "dtype", np.float32)) for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    nbytes = [sizes[i] * dtypes[i].itemsize for i in range(len(leaves))]
    total = sum(nbytes)
    if bucket_bytes is not None:
        if num_buckets is not None:
            raise ValueError("pass num_buckets OR bucket_bytes, not both")
        num_buckets = max(1, -(-total // int(bucket_bytes)))
    elif num_buckets is None:
        num_buckets = DEFAULT_NUM_BUCKETS
    num_buckets = max(1, min(int(num_buckets), len(leaves)))

    # dtype groups, largest byte-share first (stable order via dtype str)
    groups = {}
    for i, dt in enumerate(dtypes):
        groups.setdefault(str(dt), []).append(i)
    ordered = sorted(groups.items(),
                     key=lambda kv: (-sum(nbytes[i] for i in kv[1]),
                                     kv[0]))
    # buckets per group: proportional to bytes, >=1 each, sum == requested
    # (when fewer buckets than groups, the request grows to one/group)
    counts = []
    remaining = max(num_buckets, len(ordered))
    for gi, (_, idxs) in enumerate(ordered):
        left = len(ordered) - gi - 1
        share = sum(nbytes[i] for i in idxs) / max(total, 1)
        want = max(1, min(len(idxs), round(share * num_buckets),
                          remaining - left))
        counts.append(want)
        remaining -= want

    buckets = []
    for (_, idxs), k in zip(ordered, counts):
        k = min(k, len(idxs))
        loads = [0] * k
        members = [[] for _ in range(k)]
        for i in sorted(idxs, key=lambda i: (-nbytes[i], i)):  # LPT
            b = min(range(k), key=lambda j: (loads[j], j))
            loads[b] += nbytes[i]
            members[b].append(i)
        # deterministic intra-bucket order: tree_flatten order
        buckets.extend(sorted(m) for m in members)
    # stable bucket order: by first leaf index, so bucket identity (and
    # its checkpointed encoder state) is a pure function of the tree
    buckets.sort(key=lambda b: b[0])
    return BucketPlan(treedef, shapes, dtypes, buckets)


# ===================== HLO structural overlap check =====================
# dense wire rides all-reduce; the sparse token wire rides all-gather —
# both count as "the bucket's collective" for the overlap structure
_COLLECTIVE_RE = re.compile(
    r"=\s+\S+\s+(all-reduce-start|all-reduce"
    r"|all-gather-start|all-gather)\(")


def _entry_lines(hlo_text):
    """The scheduled ENTRY computation's instruction lines, in order."""
    lines, inside = [], False
    for ln in hlo_text.splitlines():
        if ln.startswith("ENTRY "):
            inside = True
            continue
        if inside:
            if ln.startswith("}"):
                break
            lines.append(ln)
    return lines


def check_overlap_structure(hlo_text, num_buckets,
                            require_async=False):
    """Structural proof, on compiled/scheduled HLO text, that the
    bucketed exchange is overlappable AND actually scheduled overlapped:

    1. exactly `num_buckets` bucket collectives exist (the monolithic
       all-reduce really was split) — identified by the
       `dl4j_bucket{k}_exchange` named-scope stamp in op metadata;
    2. for every k >= 1, bucket k's ENCODE compute is scheduled AFTER
       bucket k-1's collective was issued (all-reduce-start on async
       backends; the sync all-reduce on CPU) — i.e. collective k-1 is
       in flight while encode k computes, never "all encodes first,
       then all collectives back-to-back".

    `require_async=True` additionally demands `all-reduce-start` ops
    (TPU/GPU latency-hiding); the CPU backend lowers collectives
    synchronously, so tier-1 asserts the schedule shape only.

    Returns a list of human-readable violations (empty == pass).
    """
    lines = _entry_lines(hlo_text)
    if not lines:
        return ["no ENTRY computation found in HLO text"]
    coll_pos = {}       # bucket -> line index of its collective
    enc_pos = {}        # bucket -> first line index of its encode ops
    for idx, ln in enumerate(lines):
        is_coll = _COLLECTIVE_RE.search(ln) is not None
        for b in range(num_buckets):
            if is_coll and b not in coll_pos \
                    and EXCHANGE_SCOPE.format(b=b) in ln:
                coll_pos[b] = idx
            if b not in enc_pos and ENCODE_SCOPE.format(b=b) in ln \
                    and not is_coll:
                enc_pos[b] = idx
    problems = []
    missing = [b for b in range(num_buckets) if b not in coll_pos]
    if missing:
        problems.append(
            f"expected one collective per bucket, none found for "
            f"buckets {missing} (split failed or scopes were fused "
            f"away)")
        return problems
    if require_async and "all-reduce-start" not in hlo_text:
        problems.append("no async all-reduce-start ops (backend lowered "
                        "collectives synchronously)")
    for b in range(1, num_buckets):
        if b not in enc_pos:
            # encode fused INTO the collective's operand producer: treat
            # the collective itself as the encode position
            enc_pos[b] = coll_pos[b]
        if enc_pos[b] <= coll_pos[b - 1]:
            problems.append(
                f"bucket {b}'s encode (line {enc_pos[b]}) is scheduled "
                f"before bucket {b - 1}'s collective (line "
                f"{coll_pos[b - 1]}) — the exchange is serialized after "
                f"all compute, nothing can overlap")
    return problems


_DEF_RE = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_NAME_RE = re.compile(r"%?([\w.\-]+)")


def check_exchange_independence(hlo_text, num_buckets):
    """What makes the bucketed exchange OVERLAPPABLE, read from data
    dependence and not from the order in which a backend happens to
    print its schedule:

    1. one collective per bucket exists (`dl4j_bucket{k}_exchange`);
    2. bucket k's collective does not depend on the encode of any LATER
       bucket — so a scheduler is free to issue it while those encodes
       still compute. (Whether it does is the backend's choice and a
       chip measurement; `check_overlap_structure` reads one backend's
       answer off its schedule text.)

    Returns a list of human-readable violations (empty == pass)."""
    defs = {}       # instruction name -> (line, operand names)
    for ln in _entry_lines(hlo_text):
        m = _DEF_RE.match(ln)
        if m:
            rhs = ln[m.end():].split(", metadata=")[0]
            defs[m.group(1)] = (ln, _NAME_RE.findall(rhs))
    if not defs:
        return ["no ENTRY computation found in HLO text"]
    problems = []
    for b in range(num_buckets):
        colls = [n for n, (ln, _) in defs.items()
                 if _COLLECTIVE_RE.search(ln)
                 and EXCHANGE_SCOPE.format(b=b) in ln]
        if not colls:
            problems.append(f"no collective found for bucket {b} (split "
                            f"failed or scopes were fused away)")
            continue
        seen, stack = set(), list(colls)
        while stack:
            for o in defs[stack.pop()][1]:
                if o in defs and o not in seen:
                    seen.add(o)
                    stack.append(o)
        for later in range(b + 1, num_buckets):
            scope = ENCODE_SCOPE.format(b=later)
            if any(scope in defs[n][0] for n in seen):
                problems.append(
                    f"bucket {b}'s collective depends on bucket {later}'s "
                    f"encode — it cannot issue until that finishes")
    return problems
