"""Sparse ragged gradient wire format (ISSUE 17): per-bucket
(index, sign) int32 payloads over a size-prefixed allgather, with
decode-and-accumulate.

The contract under test:
- encode→decode is BIT-identical to the dense {−t,0,+t} exchange
  whenever nothing overflows capacity (same shipped set, same residual
  update, same adaptive-threshold trajectory);
- wire bytes track the measured nnz ledger (≤ 2× the (index,sign)
  cost at a capacity that admits the shipped set), not the parameter
  count;
- corruption is CONTAINED: host-side `check_payload` raises the typed
  `WireFormatError`, the in-jit decode poisons the delivered gradient
  to NaN (guardian-gated step), and the scatter can never write out of
  bounds;
- the `wire.decode` fault site (faults.WIRE_DECODE) drives the same
  containment through the production trainer hook;
- the per-bucket allgather keeps the overlap structure the bucketed
  dense exchange established.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.parallel import compression as comp
from deeplearning4j_tpu.parallel.buckets import check_exchange_independence
from deeplearning4j_tpu.parallel.multihost import (MultiHostTrainer,
                                                   global_batch)
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.errors import WireFormatError


def _loss_fn(p, batch, rng):
    h = jnp.tanh(batch["x"] @ p["W1"])
    return jnp.mean(h * h)


def _params():
    r = np.random.default_rng(0)
    return {"W1": (r.standard_normal((6, 5)) * 0.5).astype(np.float32)}


def _batch(tr, step):
    r = np.random.default_rng(100 + step)
    return global_batch(tr.mesh,
                        {"x": r.standard_normal((8, 6)).astype(np.float32)})


def _trainer(wire, capacity=1.0, threshold=1e-4, buckets=None):
    return MultiHostTrainer(_loss_fn, Sgd(0.3), compress=True, wire=wire,
                            wire_capacity=capacity, buckets=buckets,
                            compression_kw={"initial_threshold": threshold})


def _bits(tree):
    return [np.asarray(jax.device_get(leaf)).view(np.int32)
            for leaf in jax.tree_util.tree_leaves(tree)]


# ===================== unit: capacity / payload =========================
def test_wire_capacity_and_payload_bytes():
    assert comp.wire_capacity(1000, 0.05) == 50
    assert comp.wire_capacity(10, 0.0001) == 1          # floor of 1
    assert comp.wire_capacity(10, 1.0) == 10            # never > bucket
    assert comp.wire_capacity(7, 0.5) == 4              # ceil
    # one int32 slot per token + [count, threshold_bits] header
    assert comp.wire_payload_bytes(50) == (50 + comp.WIRE_HEADER) * 4


def test_sparse_encode_decode_roundtrip_bit_equal():
    """One worker's payload decodes to EXACTLY the dense encoder's
    {−t,0,+t} contribution, and the encoder state update (residual,
    adaptive threshold) matches the dense rule bit for bit when
    capacity admits the shipped set."""
    r = np.random.default_rng(3)
    flat = jnp.asarray(r.standard_normal(64).astype(np.float32) * 1e-3)
    residual = jnp.asarray(r.standard_normal(64).astype(np.float32) * 1e-4)
    thr = jnp.float32(1e-3)
    state = {"residual": residual, "threshold": thr}

    payload, new_state = comp.sparse_encode(flat, state, capacity=64)
    decoded = comp._decode_row(payload, 64, jnp.float32)

    # dense reference: the exact branch threshold_encoding takes
    acc = flat + residual
    mask = jnp.abs(acc) >= thr
    dense_sent = jnp.where(mask, jnp.sign(acc) * thr, 0.0)
    np.testing.assert_array_equal(np.asarray(decoded),
                                  np.asarray(dense_sent))
    np.testing.assert_array_equal(np.asarray(new_state["residual"]),
                                  np.asarray(acc - dense_sent))
    assert int(payload[0]) == int(jnp.sum(mask))
    # wire is size-prefixed: trailing slots beyond count are empty
    tok = np.asarray(payload[comp.WIRE_HEADER:])
    assert np.count_nonzero(tok) == int(payload[0])


def test_sparse_decode_accumulates_worker_mean():
    """K workers' payloads decode-and-accumulate to the mean of their
    dense contributions (the delivered gradient of the exchange)."""
    r = np.random.default_rng(5)
    rows, dense = [], []
    for w in range(4):
        flat = jnp.asarray(r.standard_normal(32).astype(np.float32) * 1e-3)
        state = {"residual": jnp.zeros(32, jnp.float32),
                 "threshold": jnp.float32(1e-3)}
        payload, _ = comp.sparse_encode(flat, state, capacity=32)
        rows.append(payload)
        mask = jnp.abs(flat) >= 1e-3
        dense.append(jnp.where(mask, jnp.sign(flat) * 1e-3, 0.0))
    out = comp.sparse_decode(jnp.stack(rows), 32, jnp.float32)
    ref = sum(dense[1:], dense[0]) / 4
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ===================== trainer: bit-identity ============================
def test_sparse_trainer_bit_identical_to_dense(devices8):
    """THE wire acceptance: at fixed membership and a capacity that
    admits the shipped set, N steps of the sparse-wire trainer produce
    BIT-identical params, encoder residuals and thresholds to the dense
    exchange — the format changes the bytes on the wire, never the
    training trajectory."""
    runs = {}
    for wire in ("dense", "sparse"):
        tr = _trainer(wire)
        p, s = tr.init(_params())
        key = jax.random.PRNGKey(7)
        loss = None
        for step in range(10):
            p, s, loss = tr.fit_batch(p, s, _batch(tr, step),
                                      jax.random.fold_in(key, step))
        runs[wire] = (p, s, float(np.asarray(jax.device_get(loss))))

    (pd, sd, ld), (ps, ss, ls) = runs["dense"], runs["sparse"]
    for a, b in zip(_bits(pd), _bits(ps)):
        np.testing.assert_array_equal(a, b)        # params, bit level
    for a, b in zip(_bits(sd["encoder"]["residual"]),
                    _bits(ss["encoder"]["residual"])):
        np.testing.assert_array_equal(a, b)        # residuals, bit level
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(sd["encoder"]["threshold"])),
        np.asarray(jax.device_get(ss["encoder"]["threshold"])))
    assert ld == ls


def test_sparse_capacity_overflow_stays_in_residual(devices8):
    """Below-capacity wire: overflowing elements are NOT silently
    dropped — they stay in the residual (shipped after the threshold
    boosts), so the wire never lies about what was delivered."""
    tr = _trainer("sparse", capacity=2)            # 2 tokens per worker
    p, s = tr.init(_params())
    key = jax.random.PRNGKey(7)
    for step in range(4):
        p, s, _ = tr.fit_batch(p, s, _batch(tr, step),
                               jax.random.fold_in(key, step))
    stats = tr.encoder_stats(s)
    assert stats["wire_capacity"] == [2]
    # residual kept the un-shipped mass and the params stayed finite
    assert stats["residual_norm"] > 0
    assert np.isfinite(np.asarray(jax.device_get(p["W1"]))).all()


def test_wire_bytes_track_nnz(devices8):
    """Wire-cost acceptance: at a capacity sized to the shipped set,
    the sparse wire bytes are ≤ 2× the measured nnz cost (4 bytes per
    (index,sign) token) + the fixed per-message headers — and a
    sparse regime beats the dense exchange by the sparsity factor."""
    tr = _trainer("sparse", capacity=1.0)
    p, s = tr.init(_params())
    key = jax.random.PRNGKey(7)
    for step in range(3):
        p, s, _ = tr.fit_batch(p, s, _batch(tr, step),
                               jax.random.fold_in(key, step))
    stats = tr.encoder_stats(s)
    workers = int(np.asarray(s["encoder"]["threshold"]).shape[0])
    buckets = len(stats["wire_capacity"])
    header_bytes = comp.WIRE_HEADER * 4 * workers * buckets
    nnz_cost = stats["nnz"] * 4                    # (index,sign) tokens
    assert stats["wire_bytes"] <= 2 * nnz_cost + header_bytes
    # sparse regime: high threshold → few tokens → wire << dense
    tr2 = _trainer("sparse", capacity=3, threshold=10.0)
    p2, s2 = tr2.init(_params())
    for step in range(2):
        p2, s2, _ = tr2.fit_batch(p2, s2, _batch(tr2, step),
                                  jax.random.fold_in(key, step))
    st2 = tr2.encoder_stats(s2)
    assert st2["wire_bytes"] < st2["dense_bytes"]


# ===================== corruption containment ===========================
def test_check_payload_typed_errors():
    """Host-side validation names every structural violation with the
    typed WireFormatError (the chaos/recovery path's contract)."""
    state = {"residual": jnp.zeros(16, jnp.float32),
             "threshold": jnp.float32(1e-3)}
    payload, _ = comp.sparse_encode(
        jnp.asarray(np.linspace(-1, 1, 16, dtype=np.float32)), state,
        capacity=8)
    comp.check_payload(payload, 16, capacity=8)    # clean passes
    p = np.asarray(payload).copy()

    with pytest.raises(WireFormatError, match="truncated"):
        comp.check_payload(p[:1], 16)
    with pytest.raises(WireFormatError, match="size"):
        comp.check_payload(p[:-1], 16, capacity=8)
    bad = p.copy()
    bad[0] += 3                                    # count lies
    with pytest.raises(WireFormatError, match="count"):
        comp.check_payload(bad, 16, capacity=8)
    bad = p.copy()
    bad[1] = np.float32(np.nan).view(np.int32)     # nonsense threshold
    with pytest.raises(WireFormatError, match="threshold"):
        comp.check_payload(bad, 16, capacity=8)
    bad = p.copy()
    bad[comp.WIRE_HEADER] = 999                    # index out of range
    with pytest.raises(WireFormatError, match="range"):
        comp.check_payload(bad, 16, capacity=8)


def test_corrupt_payload_poisons_decode_to_nan():
    """In-jit containment: a structurally corrupt message NaN-poisons
    that worker's decoded contribution (the guardian gates the step),
    and an out-of-range token can never scatter out of bounds."""
    state = {"residual": jnp.zeros(16, jnp.float32),
             "threshold": jnp.float32(1e-3)}
    payload, _ = comp.sparse_encode(
        jnp.asarray(np.linspace(-1, 1, 16, dtype=np.float32)), state,
        capacity=8)
    clean = np.asarray(comp._decode_row(payload, 16, jnp.float32))
    assert np.isfinite(clean).all()

    for mutate in (lambda p: p.at[0].add(3),          # count mismatch
                   lambda p: p.at[1].set(              # thr = NaN bits
                       jnp.asarray(np.float32(np.nan).view(np.int32))),
                   lambda p: p.at[comp.WIRE_HEADER].set(999)):  # range
        out = np.asarray(comp._decode_row(mutate(payload), 16,
                                          jnp.float32))
        assert np.isnan(out).all(), "corruption must poison, not pass"


def test_wire_decode_fault_site_containment(devices8):
    """The faults.WIRE_DECODE site drives the corrupt-message chaos
    through the production hook: the injected WireFormatError surfaces
    typed from the sparse trainer's step, and after the plan clears the
    SAME trainer keeps training — containment, no poisoned state."""
    tr = _trainer("sparse")
    p, s = tr.init(_params())
    key = jax.random.PRNGKey(7)
    p, s, _ = tr.fit_batch(p, s, _batch(tr, 0), jax.random.fold_in(key, 0))
    plan = faults.FaultPlan(seed=0).fail_at(
        faults.WIRE_DECODE, 1,
        exc=lambda site, n: WireFormatError(
            f"injected corrupt sparse message at {site} call {n}"))
    try:
        with plan:
            with pytest.raises(WireFormatError, match="corrupt sparse"):
                tr.fit_batch(p, s, _batch(tr, 1),
                             jax.random.fold_in(key, 1))
        assert plan.fired[faults.WIRE_DECODE] == 1
    finally:
        faults.clear_plan()
    p, s, loss = tr.fit_batch(p, s, _batch(tr, 1),
                              jax.random.fold_in(key, 1))
    assert np.isfinite(float(np.asarray(jax.device_get(loss))))


# ===================== HLO structure ====================================
def test_sparse_exchange_hlo_allgather_and_overlap(devices8):
    """The sparse exchange compiles to one ALLGATHER collective per
    bucket (size-prefixed payloads, not a dense all-reduce), and keeps
    what makes the bucketed exchange overlappable: bucket k's collective
    does not depend on the encode of any later bucket. (The ORDER in
    which XLA:CPU prints its schedule is that backend's choice and
    changed with the installed XLA; data dependence is the property.)"""
    tr = _trainer("sparse", buckets=3)
    p, s = tr.init({"W1": _params()["W1"],
                    "W2": np.zeros((5, 4), np.float32),
                    "W3": np.zeros((4, 3), np.float32)})
    batch = _batch(tr, 0)
    hlo = tr.make_step().lower(
        p, s, batch, jax.random.PRNGKey(0)).compile().as_text()
    assert "all-gather" in hlo
    assert check_exchange_independence(hlo, 3) == []
    # and the checker itself rejects an exchange that waits for a later
    # bucket's encode (here through an intermediate op)
    dependent = "\n".join(
        ["ENTRY %main () -> f32[] {",
         '  %e0 = f32[4] fusion(), metadata={op_name="a/dl4j_bucket0_encode/x"}',
         '  %e1 = f32[4] fusion(), metadata={op_name="a/dl4j_bucket1_encode/x"}',
         "  %j = f32[4] add(%e0, %e1)",
         '  %a0 = f32[4] all-gather(%j), metadata={op_name="a/dl4j_bucket0_exchange/x"}',
         '  %a1 = f32[4] all-gather(%e1), metadata={op_name="a/dl4j_bucket1_exchange/x"}',
         "}"])
    assert len(check_exchange_independence(dependent, 2)) == 1
