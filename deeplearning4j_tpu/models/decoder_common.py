"""What the served language models of this package share outside their
attention: the declaration of a slot's decode state (`Leaf`), the
embedding, the norm, rotary in the rotate-half layout, the head, the run
of tokens an expert layer takes at once, the sigmoid-routed expert layer (with or without a shared SwiGLU)
that `deepseek_v3.py`, `afmoe.py` and `lfm2_moe.py` compute, and the two
steps of a causal convolution's tail that `nemotron_h.py` and
`lfm2_moe.py` keep. One place, so that no model's file imports
another's."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.kernels.flash_attention import prefill_grid_steps
from deeplearning4j_tpu.parallel.moe import (routed_calls, routed_experts,
                                             takes_kernel)

#: tokens one run of an expert layer takes off the kernel: the ragged
#: path's pairs' rows and results are (tokens x top_k) x hidden, a gigabyte
#: at 16384 tokens. The kernel takes a whole prompt, and cuts it itself
#: into as few runs as its VMEM allows (`grouped_matmul.rows_a_call`)
MOE_CHUNK = 2048


class Leaf(NamedTuple):
    """One of a layer's leaves of a slot's decode state, as a family
    declares it (`decode_leaves`): its cache key, the shape past its row
    axis and its dtype, and what a cache rung of C positions makes of its
    rows: `C / per_row` (positions packed `per_row` to a row), at most
    `cap` (a ring), or none (`per_row=0`: a state or a tail of a fixed
    shape, `width` all of it past the slot axis, that no rung touches).
    `scope` is where a prefill grafts a layer's leaves."""
    name: str
    width: tuple
    dtype: object
    per_row: int = 1
    cap: int | None = None
    scope: str = "attn/kv_write"

    def shape(self, slots, rung):
        """The leaf's shape in a cache of `slots` slots at rung `rung`."""
        if not self.per_row:
            return (slots,) + self.width
        if rung % self.per_row:
            raise ValueError(
                f"the {self.name!r} leaf packs {self.per_row} positions a "
                f"row: cache rung {rung} must be "
                + ("even" if self.per_row == 2
                   else f"a multiple of {self.per_row}"))
        rows = int(rung) // self.per_row
        return (slots, rows if self.cap is None
                else min(self.cap, rows)) + self.width


def slot_index(pos):
    """Rows at per-slot positions `pos` ((S,) or an (S, d) block) of a
    slot-contiguous cache: the slot index to pair with `pos`."""
    return jnp.arange(pos.shape[0]).reshape((-1,) + (1,) * (pos.ndim - 1))


def write_rows(leaves, slot, at, rows):
    """Each leaf's new rows `rows` (..., W) at (`slot`, `at`): whole rows,
    in place."""
    return tuple(leaf.at[slot, at].set(row.astype(leaf.dtype))
                 for leaf, row in zip(leaves, rows))


def embed(cfg, params, ids):
    """The embedding rows of `ids`, unscaled."""
    return jnp.take(params["embed"], ids, axis=0)


def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight).astype(x.dtype)


def logits(cfg, params, x):
    """The final norm and the head over hidden rows `x` (..., H), float32."""
    with jax.named_scope("logits"):
        u = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
        return jnp.dot(u, params["head"].astype(u.dtype),
                       preferred_element_type=jnp.float32)


def rope_tables(cfg, positions):
    """(cos, sin) (T, D/2) float32 at `positions` (T,): angle j is `t
    theta^(-2j/D)` over a head of D = `cfg.head_dim` lanes."""
    half = cfg.head_dim // 2
    ang = positions.astype(jnp.float32)[:, None] * cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, table):
    """Rotary in the rotate-half layout over heads `x` (T, heads, W): lane
    i turns with lane i + W/2 by the table's angle i, in float32."""
    cos, sin = (a[:, None, :] for a in table)
    x32 = x.astype(jnp.float32)
    a, b = jnp.split(x32, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def swiglu(x, gate, up, down):
    """`(silu(x W_g) * (x W_u)) W_d`, the products in x's dtype."""
    dt = x.dtype
    return (jax.nn.silu(x @ gate.astype(dt)) * (x @ up.astype(dt))) \
        @ down.astype(dt)


def real_rows(plen):
    """A prompt's real rows `plen` (a scalar) as the `lengths` of
    `flash_attention`'s one example; None stays None (the whole bucket)."""
    return None if plen is None else jnp.reshape(plen, (1,)).astype(
        jnp.int32)


def prefill_attn_pairs(impl, plen, bucket, block, windows):
    """(run, static): the grid steps of one head of the causal prefill
    kernel over an admit of `plen` real rows in a bucket of `bucket`, and
    those the bucket's static grid holds, summed over the layers that call
    it, each given by its window (None: the whole triangle); tiles of
    `min(block, bucket)` (`flash_attention.prefill_grid_steps`). None where
    `impl` does not take the kernel ('dense', or 'auto' off a TPU)."""
    if impl == "dense" or (impl == "auto"
                           and jax.default_backend() != "tpu"):
        return None
    run, static = zip(*(prefill_grid_steps(plen, bucket, block, block, w)
                        for w in windows))
    return sum(run), sum(static)


def router_scores(layer, g):
    """The router's sigmoid over ALL experts for tokens `g` (T, H),
    float32."""
    return jax.nn.sigmoid(jnp.dot(g, layer["router"].astype(g.dtype),
                                  preferred_element_type=jnp.float32))


def moe(cfg, layer, g):
    """The expert layer for tokens `g` (T, H): this chip's part of the
    routed experts (those of `cfg.experts_held`, `cfg.num_experts_per_tok`
    of them a token by `s + router_bias`; the weights the chosen `s`
    normalised over all the chosen, times `cfg.routed_scaling_factor`) and
    the shared experts whole, where the layer has them (`s_gate`). Returns
    (out (T, H) float32, the `routed_experts` counts). Prefill and decode
    run the same function; a long sequence goes through in `_runs` runs.
    The shared product comes first and is the routed experts' starting
    value: the expert kernel adds into it, so no sum of the two follows;
    without one the sum starts from zero. The normalisation adds
    `cfg.route_norm_eps` to the chosen scores' sum where the family names
    one, 1e-20 elsewhere."""
    eps = getattr(cfg, "route_norm_eps", 1e-20)

    def run(tokens):
        with jax.named_scope("router"):
            scores = router_scores(layer, tokens)
        shared = None
        if "s_gate" in layer:
            with jax.named_scope("shared"):
                shared = swiglu(tokens, layer["s_gate"], layer["s_up"],
                                layer["s_down"]).astype(jnp.float32)
        with jax.named_scope("experts"):
            return routed_experts(
                tokens, scores, layer["router_bias"], layer["w_up"],
                layer["w_down"], cfg.experts_held, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, jax.nn.silu,
                w_gate=layer["w_gate"], init=shared, norm_eps=eps)

    t = g.shape[0]
    runs = _runs(layer, t)
    if runs == 1:
        return run(g)
    out, counts = lax.map(run, g.reshape(runs, t // runs, -1))
    return out.reshape(t, -1), jnp.concatenate(
        [counts[:, :2].sum(0), counts[:, 2:].max(0)])


def _runs(layer, t):
    """The runs `moe` cuts t tokens into: one where `routed_experts` takes
    the kernel, which cuts what one of its calls cannot hold itself; else
    runs of `MOE_CHUNK` where t is a longer multiple of it."""
    if takes_kernel(layer["w_up"], layer["w_down"]) or t <= MOE_CHUNK \
            or t % MOE_CHUNK:
        return 1
    return t // MOE_CHUNK


def expert_layer(params):
    """The first of a model's layers that routes to experts."""
    return next(layer for layer in params["layers"] if "router" in layer)


def expert_runs(cfg, layer, t):
    """How many times `moe` over t tokens reads the held experts' weights:
    its runs, each as many times as `routed_experts` reads them."""
    runs = _runs(layer, t)
    return runs * routed_calls(
        t // runs, cfg.num_experts_per_tok, layer["router"].shape[1],
        cfg.compute_dtype, layer["w_up"], layer["w_down"], layer["w_gate"])


# -- a causal convolution's tail ---------------------------------------------
def conv_tail(padded, plen, rows):
    """What a causal convolution of `rows + 1` taps carries past each
    sequence's last REAL position: the `rows` rows before `plen` (B,) of
    `padded` (B, rows + T, C), the sequences left-padded with `rows` zero
    rows (so a prompt shorter than the tail keeps zeros before position 0)
    and right-padded past `plen`. Returns (B, rows, C)."""
    return jax.vmap(lambda seq, at: lax.dynamic_slice_in_dim(
        seq, at, rows, axis=0))(padded, plen)


def conv_shift(tail, row, conv):
    """One decode step of a causal convolution of K taps: the slots' `tail`
    (S, K-1, C) and the new row `row` (S, C) make the window of the K rows
    ending at the new one (S, K, C); returns (`conv` of that window, the
    tail after it: the window without its oldest row)."""
    window = jnp.concatenate([tail, row[:, None].astype(tail.dtype)], axis=1)
    return conv(window), window[:, 1:]
