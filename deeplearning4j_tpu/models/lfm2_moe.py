"""Liquid AI's LFM2-MoE language model (`model_type` lfm2_moe, as
LFM2-24B-A2B publishes it): layers of two kinds, GATED SHORT CONVOLUTIONS
(`layer_types[i] == "conv"`) and grouped-query attention
(`"full_attention"`), each followed by a feed-forward: a dense SwiGLU below
`num_dense_layers`, sigmoid-routed SwiGLU experts after it, with no shared
expert.

The sixth model family beside `models/bert.py`, `models/nemotron_h.py`,
`models/keye_vl.py`, `models/deepseek_v3.py` and `models/afmoe.py`.
Embedding `x_0 = E[id]`, unscaled. A layer over `x` (T, H) at positions t
is pre-norm: `h = x + mixer(RMSNorm_op(x))`, `y = h + ffn(RMSNorm_ffn(h))`.

- a conv layer over `u`: `[B | C | xx] = u W_in` (H -> 3H, split in that
  order), `z = B * xx`, `c_t = sum_j w[:, j] z_{t-K+1+j}` (a causal
  depthwise convolution of K = `conv_L_cache` taps, z zero before position
  0, no bias, no activation), out `(C * c) W_out`.
- an attention layer: `q = u W_q` (Hq heads of D = H / Hq), `k = u W_k`,
  `v = u W_v` (Hkv heads); q and k RMSNorm over each head's D lanes (one
  weight of D shared by the heads); RoPE in halves (lane j with lane j +
  D/2, angle `t theta^(-2j/D)`); query head h reads KV head h // (Hq /
  Hkv), float32 softmax of `q . k / sqrt(D)` over `s <= t`; out
  `concat_h(o_h) W_o`.
- the feed-forward `(silu(m W_1) * (m W_3)) W_2` below `num_dense_layers`;
  after it `decoder_common.moe` (`sigmoid(m W_r)` over ALL experts in
  float32, the top-k of `s + b` with `b` for the CHOICE only, weights `s_e
  / (sum of the chosen s + 1e-6)` times `routed_scaling_factor`, the
  experts HELD here through `parallel.moe.routed_experts`).
- after the last layer, RMSNorm and the head TIED to the embedding: `u
  E^T`.

A conv layer keeps nothing a position: a SLOT keeps the last K-1 rows of
z, all that the convolution reads. An attention layer keeps a position's k
(normed and rotated) and v. `generation/decode.py`'s `Lfm2MoeDecoder`
holds both kinds in one cache (`decode_leaves`).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels.flash_attention import (
    flash_attention, flash_attention_decode)
# (the embedding, RMSNorm, the SwiGLU, the sigmoid-routed expert layer and
# a causal convolution's tail are every served decoder's)
from deeplearning4j_tpu.models.decoder_common import (  # noqa: F401
    Leaf, conv_shift, conv_tail, embed, expert_runs, moe, real_rows,
    rms_norm, rope_tables, rotate, swiglu, write_rows)

#: rows of queries and of keys a grid step of a prompt's causal attention
#: takes (the scores of a 1024 x 1024 tile are 4 MB of float32)
PREFILL_BLOCK = 1024

_KINDS = ("conv", "full_attention")


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int          # a dense layer's feed-forward
    moe_intermediate_size: int      # an expert's
    num_experts: int                # the router's outputs: ALL experts
    experts_held: tuple             # (first, count) of those held here
    num_experts_per_tok: int
    num_dense_layers: int
    layer_types: tuple              # the layers held: "conv" or attention
    conv_L_cache: int = 3           # the convolution's taps
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: str = "float32"          # compute and weight dtype
    #: added to the chosen scores' sum before the routing weights are
    #: divided by it: the family's `Lfm2MoeSparseMoeBlock` (a constant of
    #: the family, not a field)
    route_norm_eps = 1e-6

    def __post_init__(self):
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide the hidden size, KV heads "
                             "the query heads")
        if self.head_dim % 2 or self.conv_L_cache < 2:
            raise ValueError("rotary halves need an even head width; the "
                             "convolution at least 2 taps")
        if len(self.layer_types) != self.num_hidden_layers \
                or not set(self.layer_types) <= set(_KINDS):
            raise ValueError(f"layer_types must name each of the "
                             f"{self.num_hidden_layers} layers held as one "
                             f"of {_KINDS}")
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.num_experts}")

    @classmethod
    def from_dict(cls, d, **over):
        """From a `config.json`'s keys (`model_type` lfm2_moe); the first
        `num_hidden_layers` of `layer_types` are the layers held. What this
        module does not compute is refused, not ignored: a convolution
        bias, routing without the expert bias or unnormalised weights, an
        untied head, rope scaling, and layer kinds other than conv and
        full attention."""
        wants = dict(conv_bias=False, use_expert_bias=True,
                     norm_topk_prob=True, tie_word_embeddings=True,
                     rope_scaling=None)
        for key, want in wants.items():
            if d.get(key, want) != want:
                raise ValueError(f"{key} = {d[key]!r}: only {want!r} is "
                                 f"computed here")
        rope = dict(d.get("rope_parameters") or {})
        if rope.get("rope_type", "default") != "default":
            raise ValueError(f"rope_parameters {rope!r}: only the default "
                             f"rotary is computed here")
        n = int(d["num_hidden_layers"])
        types = tuple(d.get("layer_types") or ())
        if len(types) < n or not set(types[:n]) <= set(_KINDS):
            raise ValueError(f"layer_types must name every layer, each one "
                             f"of {_KINDS}")
        kw = {k: int(d[k]) for k in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok", "num_dense_layers", "conv_L_cache")}
        kw.update(layer_types=types[:n],
                  routed_scaling_factor=float(d["routed_scaling_factor"]),
                  rope_theta=float(rope.get("rope_theta",
                                            d.get("rope_theta", 1e6))),
                  norm_eps=float(d["norm_eps"]),
                  experts_held=(0, int(d["num_experts"])))
        kw.update(over)
        kw["experts_held"] = tuple(int(v) for v in kw["experts_held"])
        kw["layer_types"] = tuple(kw["layer_types"])
        return cls(**kw)

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_width(self):
        return self.num_key_value_heads * self.head_dim

    def is_attention(self, li):
        return self.layer_types[li] == "full_attention"

    def is_dense(self, li):
        return li < self.num_dense_layers


# -- parameters -------------------------------------------------------------
#: the q norm's weight in the seeded draw. q and k are RMS-normed a head, so
#: a larger W_q changes nothing: the scores' spread is the norms' weights'
#: (at 1 and 1 a score spreads by about 1 over a prompt's rows, and
#: attention over thousands of them nears their mean); at 2.5 a few hundred
#: rows of a long prompt carry a head's weight (`models/afmoe.py`, whose
#: draw this follows)
SEEDED_Q_NORM = 2.5
#: the expert bias's spread in the seeded draw. Small beside the spread of
#: the sigmoid scores (about 0.2 at the router's draw): a top-4 of 64 is
#: decided in the scores' far tail, where a bias of 0.1 would already send
#: a step's 192 pairs to 43 experts where balanced routing reaches 61
#: (`PERF.md`, section 6). A trained model's bias is whatever balanced its
#: load; a random one can only unbalance it
SEEDED_EXPERT_BIAS = 0.01


def init_params(cfg, key):
    """Seeded weights, in the compute dtype: matrices normal 0.02, the
    matrices that write into the residual stream (`W_out`, `W_o`, every
    down projection) normal 0.02 / sqrt(2 L) over the L layers held (the
    blocks are plain pre-norm: nothing norms what they add), the
    convolution's taps normal 1 / sqrt(K) (a sum of K taps that keeps z's
    scale, not an average), the embedding (and so the tied head) normal
    0.02, the q norm's weight `SEEDED_Q_NORM` and every other norm weight
    1, the expert bias normal `SEEDED_EXPERT_BIAS` float32."""
    dt = cfg.compute_dtype
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    n, k = cfg.experts_held[1], cfg.conv_L_cache
    keys = iter(jax.random.split(key, 1 + 9 * cfg.num_hidden_layers))
    into_residual = 0.02 / (2 * cfg.num_hidden_layers) ** 0.5

    def mat(*shape, std=0.02, dtype=dt):
        return std * jax.random.normal(next(keys), shape, dtype)

    def ones(m, value=1.0):
        return jnp.full((m,), value, jnp.float32)

    layers = []
    for li in range(cfg.num_hidden_layers):
        layer = dict(norm_op=ones(h), norm_ffn=ones(h))
        if cfg.is_attention(li):
            layer.update(q=mat(h, h), k=mat(h, cfg.kv_width),
                         v=mat(h, cfg.kv_width),
                         o=mat(h, h, std=into_residual),
                         q_norm=ones(cfg.head_dim, SEEDED_Q_NORM),
                         k_norm=ones(cfg.head_dim))
        else:
            layer.update(in_proj=mat(h, 3 * h),
                         conv_w=mat(h, k, std=k ** -0.5),
                         out_proj=mat(h, h, std=into_residual))
        if cfg.is_dense(li):
            layer.update(d_gate=mat(h, cfg.intermediate_size),
                         d_up=mat(h, cfg.intermediate_size),
                         d_down=mat(cfg.intermediate_size, h,
                                    std=into_residual))
        else:
            layer.update(
                router=mat(h, cfg.num_experts),
                router_bias=mat(cfg.num_experts, std=SEEDED_EXPERT_BIAS,
                                dtype=jnp.float32),
                w_gate=mat(n, h, f), w_up=mat(n, h, f),
                w_down=mat(n, f, h, std=into_residual))
        layers.append(layer)
    return {"embed": mat(cfg.vocab_size, h), "layers": layers,
            "norm_f": ones(h)}


# -- pieces -----------------------------------------------------------------
def logits(cfg, params, x):
    """The final norm and the TIED head over hidden rows `x` (..., H):
    `RMSNorm(x) E^T`, float32."""
    with jax.named_scope("logits"):
        u = rms_norm(x, params["norm_f"], cfg.norm_eps)
        return jnp.einsum("...h,vh->...v", u,
                          params["embed"].astype(u.dtype),
                          preferred_element_type=jnp.float32)


# -- the gated short convolution --------------------------------------------
def _conv_in(layer, u):
    """`[B | C | xx] = u W_in` (scope `in_proj`)."""
    with jax.named_scope("in_proj"):
        return jnp.split(u @ layer["in_proj"].astype(u.dtype), 3, axis=-1)


def _taps(layer, rows, c):
    """`C * sum_j w[:, j] rows[j]` over the K rows `rows` (oldest first,
    each (..., H)), float32, then in C's dtype."""
    w = layer["conv_w"].astype(jnp.float32)
    acc = rows[0].astype(jnp.float32) * w[:, 0]
    for j in range(1, w.shape[1]):
        acc = acc + rows[j].astype(jnp.float32) * w[:, j]
    return (c.astype(jnp.float32) * acc).astype(c.dtype)


def _conv_out(layer, y):
    with jax.named_scope("out_proj"):
        return y @ layer["out_proj"].astype(y.dtype)


def shortconv(cfg, layer, u, plen):
    """The conv mixer over ONE whole sequence `u` (T, H) whose first `plen`
    positions are real. Returns (out (T, H), the tail (K-1, H): z of the
    last K-1 REAL positions, zeros before position 0)."""
    t, k = u.shape[0], cfg.conv_L_cache
    b, c, xx = _conv_in(layer, u)
    with jax.named_scope("conv"):
        padded = jnp.pad(b * xx, ((k - 1, 0), (0, 0)))
        y = _taps(layer, [padded[j:j + t] for j in range(k)], c)
        tail = conv_tail(padded[None], jnp.reshape(plen, (1,)), k - 1)[0]
    return _conv_out(layer, y), tail


def shortconv_step(cfg, layer, u, tail):
    """The conv mixer for ONE token a slot: `u` (S, H), the slots' `tail`
    (S, K-1, H). Returns (out (S, H), the tail after the token)."""
    b, c, xx = _conv_in(layer, u)
    with jax.named_scope("conv"):
        y, tail = conv_shift(tail, b * xx, lambda w: _taps(
            layer, [w[:, j] for j in range(w.shape[1])], c))
    return _conv_out(layer, y), tail


# -- grouped-query attention ------------------------------------------------
def attention_inputs(cfg, layer, u, tables):
    """What an attention layer reads of tokens `u` (T, H) at the positions
    behind `tables`: q (T, Hq, D) and the cache rows k, v (T, Hkv·D), q and
    k normed a head (scope `qkv`) and rotated (scope `rope`)."""
    t, d, eps = u.shape[0], cfg.head_dim, cfg.norm_eps
    dt = u.dtype
    with jax.named_scope("qkv"):
        q = rms_norm((u @ layer["q"].astype(dt)).reshape(t, -1, d),
                     layer["q_norm"], eps)
        k = rms_norm((u @ layer["k"].astype(dt)).reshape(t, -1, d),
                     layer["k_norm"], eps)
        v = u @ layer["v"].astype(dt)
    with jax.named_scope("rope"):
        q, k = rotate(q, tables), rotate(k, tables)
    return q, k.reshape(t, -1), v


def sequence_attention(cfg, q, k, v, impl="auto", plen=None):
    """A whole sequence's causal attention: q (T, Hq, D), k, v (T, Hkv·D)
    -> (T, Hq·D), in scope `flash_prefill`. The kernel takes every query
    head's own K/V rows: the KV heads are repeated to the query heads
    first. impl 'auto' (the kernel on a TPU, XLA elsewhere), 'pallas' or
    'dense'. With `plen`, the sequence's real rows, the kernel runs the
    tile pairs up to it alone (`flash_attention(..., lengths=)`): the rows
    of the q tiles past it come back as zeros, every other row as without
    it."""
    t, nh, d = q.shape
    hkv = cfg.num_key_value_heads
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "dense"
    with jax.named_scope("flash_prefill"):
        if impl == "pallas":
            block = min(PREFILL_BLOCK, t)
            kh, vh = (jnp.repeat(a.reshape(t, hkv, d), nh // hkv, axis=1)
                      for a in (k, v))
            out = flash_attention(
                *(a.transpose(1, 0, 2)[None] for a in (q, kh, vh)),
                causal=True, block_q=block, block_k=block, native=True,
                lengths=real_rows(plen))
            return out[0].transpose(1, 0, 2).reshape(t, -1)
        if impl != "dense":
            raise ValueError(f"unknown attention impl {impl!r}; expected "
                             f"'auto', 'pallas' or 'dense'")
        qg = q.reshape(t, hkv, nh // hkv, d)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k.reshape(t, hkv, d),
                       preferred_element_type=jnp.float32) * d ** -0.5
        seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype),
                          v.reshape(t, hkv, d),
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype).reshape(t, -1)


def _attn_out(layer, ctx):
    with jax.named_scope("proj"):
        return ctx @ layer["o"].astype(ctx.dtype)


# -- the feed-forward -------------------------------------------------------
def feed_forward(cfg, li, layer, h):
    """`h + ffn(RMSNorm_ffn(h))` over tokens `h` (T, H): a dense SwiGLU
    below `num_dense_layers` (scope `ffn`), the expert layer after (scope
    `moe`). Returns (y, the expert layer's counts or None)."""
    with jax.named_scope("norm"):
        m = rms_norm(h, layer["norm_ffn"], cfg.norm_eps)
    if cfg.is_dense(li):
        with jax.named_scope("ffn"):
            return h + swiglu(m, layer["d_gate"], layer["d_up"],
                              layer["d_down"]), None
    with jax.named_scope("moe"):
        f, counts = moe(cfg, layer, m)
        return h + f.astype(h.dtype), counts


# -- the whole model --------------------------------------------------------
def apply_layer(cfg, li, layer, x, tables, plen, impl="auto"):
    """Layer `li` over ONE whole sequence `x` (T, H), its first `plen`
    positions real (None: all of them); returns (y, the layer's decode state: (k, v) rows (T,
    Hkv·D) of an attention layer, a conv layer's tail (K-1, H))."""
    with jax.named_scope("norm"):
        u = rms_norm(x, layer["norm_op"], cfg.norm_eps)
    if cfg.is_attention(li):
        with jax.named_scope("attn"):
            q, k, v = attention_inputs(cfg, layer, u, tables)
            x = x + _attn_out(layer, sequence_attention(cfg, q, k, v, impl,
                                                        plen))
        state = (k, v)
    else:
        with jax.named_scope("shortconv"):
            out, state = shortconv(cfg, layer, u,
                                   x.shape[0] if plen is None else plen)
            x = x + out
    return feed_forward(cfg, li, layer, x)[0], state


def encode(cfg, params, ids, plen=None, impl="auto"):
    """The full-sequence forward over ONE sequence `ids` (T,), of which the
    first `plen` (all by default) are real, up to the last layer's output
    (T, H), and every layer's decode state (`apply_layer`)."""
    t = ids.shape[0]
    with jax.named_scope("rope"):
        tables = rope_tables(cfg, jnp.arange(t))
    with jax.named_scope("embed"):
        x = embed(cfg, params, ids)
    states = []
    for li, layer in enumerate(params["layers"]):
        with jax.named_scope(f"layer{li}"):
            x, state = apply_layer(cfg, li, layer, x, tables, plen, impl)
        states.append(state)
    return x, states


# -- decode (`generation/decode.py`'s `Lfm2MoeDecoder`) ------------------------
def decode_leaves(cfg):
    """An attention layer's K and V leaves, C rows at a rung of C; a conv
    layer's TAIL `(K-1, H)`, the last K-1 rows of `B * xx` that its
    convolution reads, which no rung touches."""
    dt = cfg.compute_dtype
    kv = tuple(Leaf(name, (cfg.kv_width,), dt) for name in ("k", "v"))
    tail = (Leaf("conv", (cfg.conv_L_cache - 1, cfg.hidden_size), dt,
                 per_row=0, scope="shortconv/tail_write"),)
    return [kv if cfg.is_attention(li) else tail
            for li in range(cfg.num_hidden_layers)]


def decode_context(cfg, pos, leaves, counts):
    """The rotary tables at `pos` (S,); the tally (the expert layers'
    counts, the attention layers' rows in use) from zero."""
    with jax.named_scope("rope"):
        tables = rope_tables(cfg, pos)
    return dict(pos=pos, tables=tables, counts=counts), (
        jnp.zeros((3,), jnp.int32), jnp.zeros((), jnp.int32))


def decode_layer(cfg, li, layer, x, ctx, state, tally, impl="auto"):
    """Layer `li` for ONE token a slot, `x` (S, H): an attention layer
    writes the token's K and V rows at its position in `state` = (k, v)
    `(S, C, Hkv·D)` and attends the rows in use, `min(pos + 1, C)`; a conv
    layer shifts its tail `state` = (tail,) by the token; then the
    feed-forward."""
    pos = ctx["pos"]
    s = x.shape[0]
    counted, in_use = tally
    with jax.named_scope(f"layer{li}"):
        if cfg.is_attention(li):
            kc, vc = state
            # rows 0..pos are in use (the current write included); a lane
            # frozen at the end of its rung (pos == C) keeps all of them
            lengths = jnp.minimum(pos + 1, kc.shape[1])
        with jax.named_scope("norm"):
            u = rms_norm(x, layer["norm_op"], cfg.norm_eps)
        if cfg.is_attention(li):
            with jax.named_scope("attn"):
                q, k, v = attention_inputs(cfg, layer, u, ctx["tables"])
                with jax.named_scope("kv_write"):
                    kc, vc = write_rows((kc, vc), jnp.arange(s), pos,
                                        (k, v))
                rows = jnp.arange(kc.shape[1])[None, :] < lengths[:, None]
                out = flash_attention_decode(q, kc, vc, rows, impl=impl,
                                             lengths=lengths)
                x = x + _attn_out(layer, out.reshape(s, -1).astype(x.dtype))
            state = (kc, vc)
        else:
            with jax.named_scope("shortconv"):
                out, tail = shortconv_step(cfg, layer, u, *state)
                x = x + out
            state = (tail,)
        y, counts = feed_forward(cfg, li, layer, x)
        if cfg.is_attention(li):
            in_use = in_use + lengths.sum()
    if counts is not None:
        counted = counted + counts
    return y, state, (counted, in_use)


def decode_counts(cfg, ctx, tally):
    counted, in_use = tally
    return ctx["counts"] + jnp.concatenate([counted, in_use[None]])


def encode_prompt(cfg, params, prompt, plen, impl="auto"):
    return encode(cfg, params, prompt, plen, impl=impl)


def cache_parts(cfg, li, state, plen, leaves):
    """An attention layer's K/V rows 0..P-1; a conv layer's tail as the
    last REAL positions left it (z of positions plen - K + 1 .. plen - 1,
    zeros before 0; the bucket's last rows would be padding)."""
    return state if cfg.is_attention(li) else (state,)


def prefill_windows(cfg):
    return [None] * sum(map(cfg.is_attention, range(cfg.num_hidden_layers)))


def forward(cfg, params, ids, impl="auto"):
    """Next-token logits at every position of `ids` (T,) or (B, T), the
    rows of a batch one after the other."""
    if ids.ndim == 1:
        return logits(cfg, params, encode(cfg, params, ids, impl=impl)[0])
    return jnp.stack([forward(cfg, params, row, impl) for row in ids])
