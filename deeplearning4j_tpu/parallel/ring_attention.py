"""Ring attention — sequence/context parallelism over the `sp` mesh axis.

The reference scales long sequences by truncated BPTT; TPU-native long
context instead shards the sequence across chips and rotates K/V blocks
around the ICI ring (Liu et al., Ring Attention) with an online-softmax
accumulator, overlapping each hop with the local attention block. Used by
models/bert.py + parallel tests; single-device callers get the same math
via `blockwise_attention` (flash-style lax.scan) or `dense_attention`.

Shapes: (B, H, T, D) throughout; softmax stats accumulate in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import shard_map


def dense_attention(q, k, v, causal=False, mask=None, scale=None):
    """Reference O(T²) attention (numerics oracle for the sharded paths)."""
    d = q.shape[-1]
    scale = scale or (1.0 / jnp.sqrt(d).astype(q.dtype))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(causal_mask, logits, -jnp.inf)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _block_accumulate(carry, q, k, v, logits_mask, scale):
    """Online-softmax accumulation of one K/V block into (o, l, m)."""
    o, l, m = carry
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if logits_mask is not None:
        s = jnp.where(logits_mask, s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    return o_new, l_new, m_new


def blockwise_attention(q, k, v, block_size=512, causal=False,
                        kv_mask=None):
    """Single-device flash-style attention: lax.scan over K/V blocks with
    online softmax — O(T) memory. kv_mask (B, T): padding-key validity
    (invalid keys never receive probability), still O(T) memory."""
    b, h, t, d = q.shape
    scale = 1.0 / jnp.sqrt(d)
    nblk = -(-t // block_size)
    pad = nblk * block_size - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, h, nblk, -1, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nblk, -1, d).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(t)
    if kv_mask is not None and pad:
        kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad)))

    def step(carry, inp):
        kv_idx, kblk, vblk = inp
        k_pos = kv_idx * block_size + jnp.arange(block_size)
        lm = (k_pos[None, :] < t)
        if causal:
            lm = lm & (q_pos[:, None] >= k_pos[None, :])
        lm = lm[None, None]
        if kv_mask is not None:
            blk = lax.dynamic_slice_in_dim(kv_mask, kv_idx * block_size,
                                           block_size, 1)
            lm = lm & (blk > 0)[:, None, None, :]
        return _block_accumulate(carry, q, kblk, vblk, lm, scale), None

    o0 = jnp.zeros((b, h, t, d), jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    m0 = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    (o, l, m), _ = lax.scan(step, (o0, l0, m0),
                            (jnp.arange(nblk), kb, vb))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _make_ring_flash(axis_name, block_q=128, block_k=128, interpret=None,
                     causal=False):
    """Ring attention whose LOCAL block math is the Pallas flash kernel
    pair: forward calls the fused fwd kernel per held K/V block and merges
    the per-block (o, lse) partials with the associative logsumexp merge;
    backward is a second ring pass driving the Pallas dQ / dK-dV kernels
    with the GLOBAL lse (dk/dv partial sums ride around the ring with
    their K/V blocks and arrive home after the full cycle).

    Causal (round-4): at ring step i, shard `my` holds the K/V block of
    shard (my − i) mod n, so the block's GLOBAL position relative to the
    queries is fully determined by the step: i == 0 → the diagonal block
    (run the CAUSAL kernel), i ≤ my → strictly-past block (full kernel),
    i > my → strictly-future block (skipped: lse = −inf in the merge,
    zero grads in backward). lax.cond picks the kernel per step, so each
    step still runs exactly one Pallas program."""
    from deeplearning4j_tpu.kernels.flash_attention import (
        _flash_backward, _flash_forward, _zero_mask_cotangent)

    def _block_fwd(q, kblk, vblk, mblk, i, my):
        """One local flash block, causal- and mask-aware; lse is
        (B*H, tq_padded). mblk is None (static) or the held K/V block's
        key-validity slice."""
        if not causal:
            return _flash_forward(q, kblk, vblk, None, mblk, False,
                                  block_q, block_k, interpret)

        def diag(q, kb, vb, mb):
            return _flash_forward(q, kb, vb, None, mb, True,
                                  block_q, block_k, interpret)

        def past(q, kb, vb, mb):
            return _flash_forward(q, kb, vb, None, mb, False,
                                  block_q, block_k, interpret)

        def future(q, kb, vb, mb):
            # strictly-future block: SKIP the kernel — -inf lse zeroes
            # its weight in the associative merge. Shapes must mirror
            # _flash_forward's returns: out (B,H,T,D), lse (B*H, tq_pad).
            b, h, t_local, d = q.shape
            bq = min(block_q, max(t_local, 8))
            tq_pad = -(-t_local // bq) * bq
            return (jnp.zeros((b, h, t_local, d), q.dtype),
                    jnp.full((b * h, tq_pad), -jnp.inf, jnp.float32))

        if mblk is None:
            return lax.cond(
                i == 0, lambda q, kb, vb: diag(q, kb, vb, None),
                lambda q, kb, vb: lax.cond(
                    i <= my, lambda q2, kb2, vb2: past(q2, kb2, vb2, None),
                    lambda q2, kb2, vb2: future(q2, kb2, vb2, None),
                    q, kb, vb),
                q, kblk, vblk)
        return lax.cond(
            i == 0, diag,
            lambda q, kb, vb, mb: lax.cond(i <= my, past, future,
                                           q, kb, vb, mb),
            q, kblk, vblk, mblk)

    def _fwd_pass(q, k, v, kv_mask):
        """Shared forward ring (kv_mask None or the local mask slice):
        per-block (o, lse) partials merged -inf-safely — a block whose
        kernel saw NO valid key returns the +1e30 invalid-row sentinel,
        which means "contributes nothing" (-inf) in the merge."""
        n = lax.psum(1, axis_name)
        my = lax.axis_index(axis_name)
        b, h, t_local, d = q.shape
        perm = [(j, (j + 1) % n) for j in range(n)]

        def step(carry, i):
            o, lse, kblk, vblk, mblk = carry
            ob, lse_b = _block_fwd(q, kblk, vblk, mblk, i, my)
            lse_b = lse_b[:, :t_local].reshape(b, h, t_local)
            # +1e30 = kernel sentinel (no valid key for the row);
            # <= -1e29 = causal+masked starvation (l ~ 0 at m = -1e30).
            # Both mean "no contribution from this block".
            lse_b = jnp.where((lse_b >= 1e29) | (lse_b <= -1e29),
                              -jnp.inf, lse_b)
            m = jnp.maximum(lse, lse_b)
            m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
            w1 = jnp.where(jnp.isfinite(lse), jnp.exp(lse - m_safe), 0.0)
            w2 = jnp.where(jnp.isfinite(lse_b),
                           jnp.exp(lse_b - m_safe), 0.0)
            s = jnp.maximum(w1 + w2, 1e-30)
            o = (o * w1[..., None]
                 + ob.astype(jnp.float32) * w2[..., None]) / s[..., None]
            lse = m + jnp.log(s)
            kblk = lax.ppermute(kblk, axis_name, perm)
            vblk = lax.ppermute(vblk, axis_name, perm)
            if mblk is not None:
                mblk = lax.ppermute(mblk, axis_name, perm)
            return (o, lse, kblk, vblk, mblk), None

        o0 = jnp.zeros(q.shape, jnp.float32)
        lse0 = jnp.full((b, h, t_local), -jnp.inf, jnp.float32)
        (o, lse, _, _, _), _ = lax.scan(step, (o0, lse0, k, v, kv_mask),
                                        jnp.arange(n))
        return o, lse

    def _bwd_pass(q, k, v, kv_mask, o, lse, g):
        n = lax.psum(1, axis_name)
        my = lax.axis_index(axis_name)
        b, h, t_local, d = q.shape
        # rows that saw NO valid key anywhere merged to lse = -inf; the
        # backward recompute needs the kernels' +1e30 sentinel form so
        # p = exp(finite - 1e30) == 0 (never exp(+inf))
        lse = jnp.where(jnp.isfinite(lse), lse, 1e30)
        lse2 = lse.reshape(b * h, t_local)
        perm = [(j, (j + 1) % n) for j in range(n)]

        def _block_bwd(i, kblk, vblk, mblk):
            if not causal:
                return _flash_backward(q, kblk, vblk, None, mblk, o, lse2,
                                       g, False, block_q, block_k,
                                       interpret)

            def diag(kb, vb, mb):
                return _flash_backward(q, kb, vb, None, mb, o, lse2, g,
                                       True, block_q, block_k, interpret)

            def past(kb, vb, mb):
                return _flash_backward(q, kb, vb, None, mb, o, lse2, g,
                                       False, block_q, block_k, interpret)

            def future(kb, vb, mb):
                # the global-lse recompute would give NONZERO p for
                # future blocks (they never entered the softmax) — their
                # gradients are identically zero and must be skipped
                return (jnp.zeros(q.shape, q.dtype),
                        jnp.zeros(kb.shape, kb.dtype),
                        jnp.zeros(vb.shape, vb.dtype))

            if mblk is None:
                return lax.cond(
                    i == 0, lambda kb, vb: diag(kb, vb, None),
                    lambda kb, vb: lax.cond(
                        i <= my, lambda kb2, vb2: past(kb2, vb2, None),
                        lambda kb2, vb2: future(kb2, vb2, None),
                        kb, vb),
                    kblk, vblk)
            return lax.cond(
                i == 0, diag,
                lambda kb, vb, mb: lax.cond(i <= my, past, future,
                                            kb, vb, mb),
                kblk, vblk, mblk)

        def step(carry, i):
            dq, kblk, vblk, mblk, dkblk, dvblk = carry
            dq_i, dk_i, dv_i = _block_bwd(i, kblk, vblk, mblk)
            dq = dq + dq_i.astype(jnp.float32)
            dkblk = dkblk + dk_i.astype(jnp.float32)
            dvblk = dvblk + dv_i.astype(jnp.float32)
            # dk/dv partials travel WITH their K/V blocks; after the full
            # cycle every block (and its gradient sum) is home again
            kblk = lax.ppermute(kblk, axis_name, perm)
            vblk = lax.ppermute(vblk, axis_name, perm)
            if mblk is not None:
                mblk = lax.ppermute(mblk, axis_name, perm)
            dkblk = lax.ppermute(dkblk, axis_name, perm)
            dvblk = lax.ppermute(dvblk, axis_name, perm)
            return (dq, kblk, vblk, mblk, dkblk, dvblk), None

        z = jnp.zeros(q.shape, jnp.float32)
        (dq, _, _, _, dk, dv), _ = lax.scan(
            step, (z, k, v, kv_mask, z, z), jnp.arange(n))
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    @jax.custom_vjp
    def ring_flash(q, k, v):
        o, _ = _fwd_pass(q, k, v, None)
        return o.astype(q.dtype)

    def fwd(q, k, v):
        o, lse = _fwd_pass(q, k, v, None)
        out = o.astype(q.dtype)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, o, lse = res
        return _bwd_pass(q, k, v, None, o, lse, g)

    ring_flash.defvjp(fwd, bwd)

    @jax.custom_vjp
    def ring_flash_masked(q, k, v, kv_mask):
        o, _ = _fwd_pass(q, k, v, kv_mask)
        return o.astype(q.dtype)

    def fwd_m(q, k, v, kv_mask):
        o, lse = _fwd_pass(q, k, v, kv_mask)
        out = o.astype(q.dtype)
        return out, (q, k, v, kv_mask, out, lse)

    def bwd_m(res, g):
        q, k, v, kv_mask, o, lse = res
        dq, dk, dv = _bwd_pass(q, k, v, kv_mask, o, lse, g)
        return dq, dk, dv, _zero_mask_cotangent(kv_mask)

    ring_flash_masked.defvjp(fwd_m, bwd_m)

    def ring_flash_entry(q, k, v, kv_mask=None):
        if kv_mask is None:
            return ring_flash(q, k, v)
        return ring_flash_masked(q, k, v, kv_mask)

    return ring_flash_entry


def make_ring_attention(mesh, axis_name="sp", causal=False, use_flash=None,
                        block_q=128, block_k=128, interpret=None):
    """Build a ring-attention fn for q,k,v sharded over `axis_name` on the
    time dim. Returns f(q_local, k_local, v_local) usable INSIDE shard_map
    over `mesh` — each of the n devices holds (B, H, T/n, D) and K/V blocks
    ppermute around the ring, one ICI hop per step.

    use_flash (default: auto — on TPU, noncausal): local block math runs
    the Pallas flash kernels (fwd + bwd) composed with the ring, so the
    sp path gets the fused-kernel HBM profile instead of the lax.scan
    accumulator. Causal can ride the same kernels (round-4: diagonal ring
    step → causal kernel, past steps → full kernel, future steps skipped)
    but stays OPT-IN (use_flash=True) until it has an on-chip smoke run —
    interpret-mode tests don't validate Mosaic lowering (the round-3
    lesson).

    Padded batches: BOTH paths take kv_mask (a local (B, T/n) slice
    that rotates with its K/V block). The masked FLASH ring (round-5)
    feeds each held block's slice into the kernels' own kv_mask path
    (fwd + bwd) with -inf-safe partial merging; like causal, it stays
    OPT-IN (use_flash=True) until an on-chip smoke —
    ring_attention() auto-selects the lax ring for masked batches."""
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu" and not causal
    if use_flash:
        return _make_ring_flash(axis_name, block_q, block_k, interpret,
                                causal=causal)

    def ring_attn(q, k, v, kv_mask=None):
        """kv_mask (round-5): local (B, T/n) key-validity slice — it
        rotates around the ring WITH its K/V block, so padded keys never
        receive probability from any device's queries (O(T/n) memory,
        no full-mask gather)."""
        n = lax.psum(1, axis_name)
        my = lax.axis_index(axis_name)
        b, h, t_local, d = q.shape
        scale = 1.0 / jnp.sqrt(d)
        q_pos = my * t_local + jnp.arange(t_local)

        def step(carry, i):
            o, l, m, kblk, vblk, mblk = carry
            src_idx = (my - i) % n  # whose K/V block we currently hold
            lm = None
            if causal:
                k_pos = src_idx * t_local + jnp.arange(t_local)
                lm = (q_pos[:, None] >= k_pos[None, :])[None, None]
            if mblk is not None:
                km = (mblk > 0)[:, None, None, :]   # (B,1,1,T/n)
                lm = km if lm is None else (lm & km)
            o, l, m = _block_accumulate((o, l, m), q, kblk, vblk, lm, scale)
            # rotate K/V (+ their mask slice) one hop around the ring
            # (overlaps with next block on TPU: XLA schedules the
            # collective-permute async)
            perm = [(j, (j + 1) % n) for j in range(n)]
            kblk = lax.ppermute(kblk, axis_name, perm)
            vblk = lax.ppermute(vblk, axis_name, perm)
            if mblk is not None:
                mblk = lax.ppermute(mblk, axis_name, perm)
            return (o, l, m, kblk, vblk, mblk), None

        o0 = jnp.zeros((b, h, t_local, d), jnp.float32)
        l0 = jnp.zeros((b, h, t_local), jnp.float32)
        m0 = jnp.full((b, h, t_local), -jnp.inf, jnp.float32)
        (o, l, m, _, _, _), _ = lax.scan(step, (o0, l0, m0, k, v, kv_mask),
                                         jnp.arange(n))
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    return ring_attn


def ring_attention(q, k, v, mesh, axis_name="sp", causal=False,
                   kv_mask=None):
    """Convenience wrapper: shard (B,H,T,D) over T, run the ring, gather.
    kv_mask: global (B, T) key-validity mask for padded batches — NOTE
    masked batches auto-select the lax ring (the masked flash ring
    exists but is opt-in via make_ring_attention(use_flash=True) until
    it has an on-chip smoke run)."""
    fn = make_ring_attention(mesh, axis_name, causal,
                             use_flash=False if kv_mask is not None
                             else None)
    spec = P(None, None, axis_name, None)
    args, specs = [q, k, v], [spec, spec, spec]
    if kv_mask is not None:
        args.append(kv_mask)
        specs.append(P(None, axis_name))
    shmapped = shard_map(fn, mesh=mesh, in_specs=tuple(specs),
                             out_specs=spec, check_vma=False)
    return shmapped(*args)
