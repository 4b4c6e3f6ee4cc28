"""DeepSeek-V3 style language model (`model_type` deepseek_v3, as
kanana-2-30b-a3b publishes it): multi-head latent attention without a
query low-rank, leading dense layers, then sigmoid-routed SwiGLU experts
with shared experts beside them.

The fourth model family beside `models/bert.py`, `models/nemotron_h.py` and
`models/keye_vl.py`. Every layer is pre-norm, `h = x + attn(RMSNorm(x))`,
`y = h + ffn(RMSNorm(h))`; after the last, RMSNorm and an untied head. No
biases. For a layer input `u = RMSNorm(x)` (T, H) at positions t:

- queries: `q = u W_q`, heads of `[q_nope (N), q_rope (R)]`; no low-rank,
  no norm.
- the latent: `[c_raw (L), kr_raw (R)] = u W_kva`; `c = RMSNorm(c_raw)`,
  `kr = RoPE(kr_raw)`, ONE rotary key head for all query heads. **A
  position's decode state is `(c, kr)`: L + R values** (576 of them where
  the expanded keys and values are 10240).
- RoPE over R lanes in INTERLEAVED pairs, as the source's `rope_interleave`
  says: lanes (2i, 2i + 1) turn by `t theta^(-2i/R)`. The program rotates
  the pairs where they lie (a lane's partner is its neighbour), so `W_q`'s
  and `W_kva`'s rotary columns are in the published order.
- expanded form (a whole sequence, `encode`): `k_nope, v = c W_UK, c W_UV`
  (heads of N and V), `k[h] = [k_nope[h], kr]`, causal softmax of `q[h] .
  k[h] / sqrt(N + R)`, `o[h] = sum_s p[h]_s v[h]_s`.
- absorbed form (one token against a cache, `absorbed_attention`): `q_lat[h]
  = q_nope[h] W_UK[h]^T` (L); score `(q_lat[h] . c_s + q_rope[h] . kr_s) /
  sqrt(N + R)`; `o_lat[h] = sum_s p[h]_s c_s`; `o[h] = o_lat[h] W_UV[h]`.
  The same sums in another order: `kernels/mla_attention.py` reads each
  cached row once, as key and as value.
- feed-forward: layers below `first_k_dense_replace` a dense SwiGLU; the
  rest `s = sigmoid(g W_r)` over ALL experts in float32, the top-k of `s +
  b` (`b` the correction bias, for the CHOICE only; one group), weights
  `s_e / sum of the chosen s` times `routed_scaling_factor`, expert e
  `(silu(g W_g^e) * (g W_u^e)) W_d^e` through
  `parallel.moe.routed_experts` for the experts HELD here, and the shared
  experts as ONE SwiGLU of `n_shared_experts` x the expert width on every
  token, unweighted.

`generation/decode.py`'s `MLADecoder` serves it from the decode state
declared here (`decode_leaves`) and the step's and the prefill's hooks.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels.flash_attention import flash_attention
from deeplearning4j_tpu.kernels.mla_attention import (
    latent_tile_positions, mla_attention_decode, pack_latent,
    write_latent_row)
# (the embedding, RMSNorm, the final norm and head, and the sigmoid-routed
# expert layer with its shared SwiGLU are every served decoder's)
from deeplearning4j_tpu.models.decoder_common import (  # noqa: F401
    MOE_CHUNK, Leaf, embed, expert_runs, logits, moe, real_rows, rms_norm,
    router_scores, swiglu)

#: rows of queries and of keys a grid step of a prompt's attention takes.
#: On a v5e at 32 heads, keys of 192 and values of 128, bfloat16 (`PERF.md`,
#: PR 37): 46.6 ms a call at 512 x 512 and 16384 tokens, 34.8 at 512 x
#: 1024, 46.6 at 1024 x 512, 29.0 at 1024 x 1024 (8192 tokens: 13.1, 10.1,
#: 13.3, 8.6); the scores of a 1024 x 1024 tile are 4 MB of float32
PREFILL_BLOCK = 1024


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int               # L: the latent's width
    qk_nope_head_dim: int           # N
    qk_rope_head_dim: int           # R
    v_head_dim: int                 # V
    intermediate_size: int          # a dense layer's feed-forward
    moe_intermediate_size: int      # an expert's
    n_routed_experts: int           # the router's outputs: ALL experts
    experts_held: tuple             # (first, count) of those held here
    num_experts_per_tok: int
    n_shared_experts: int
    first_k_dense_replace: int
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    dtype: str = "float32"          # compute and weight dtype

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary pairs need an even qk_rope_head_dim")
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_routed_experts}")

    @classmethod
    def from_dict(cls, d, **over):
        """From a `config.json`'s keys (`model_type` deepseek_v3). The
        file's `n_routed_experts` is the router's width; `experts_held`
        defaults to all of them. What this module does not compute is
        refused, not ignored."""
        wants = dict(q_lora_rank=None, rope_scaling=None,
                     scoring_func="sigmoid", topk_method="noaux_tc",
                     n_group=1, topk_group=1, norm_topk_prob=True,
                     moe_layer_freq=1, rope_interleave=True,
                     attention_bias=False, hidden_act="silu")
        for key, want in wants.items():
            if d.get(key, want) != want:
                raise ValueError(f"{key} = {d[key]!r}: only {want!r} is "
                                 f"computed here")
        kw = {k: int(d[k]) for k in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "intermediate_size",
            "moe_intermediate_size", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts",
            "first_k_dense_replace")}
        kw.update(routed_scaling_factor=float(d["routed_scaling_factor"]),
                  rope_theta=float(d["rope_theta"]),
                  rms_norm_eps=float(d["rms_norm_eps"]),
                  experts_held=(0, int(d["n_routed_experts"])))
        kw.update(over)
        kw["experts_held"] = tuple(int(v) for v in kw["experts_held"])
        return cls(**kw)

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self):
        """Values a cached position holds a layer: c and kr."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attn_scale(self):
        return self.qk_head_dim ** -0.5

    def is_dense(self, li):
        return li < self.first_k_dense_replace


# -- parameters -------------------------------------------------------------
#: how much larger than its neighbours the seeded draw makes `q` (scores of
#: a spread of 2.3 over a prompt's rows where 0.02 gives 0.6: about a
#: hundred rows of 4096 carry a head's weight, and which depends on what
#: the cache holds) and `o` (what those rows say reaches the residual stream at
#: a third of the token's own embedding). At 1 and 1 attention averages
#: thousands of rows into nothing and no token served depends on the cache
#: (`PERF.md`, Findings, PR 37: the reference with its attention left out
#: chose the same tokens); at 4 and 20 every late position says the same
SEEDED_Q_GAIN = 4.0
SEEDED_O_GAIN = 4.0


def init_params(cfg, key):
    """Seeded weights, in the compute dtype, drawn as `keye_vl.init_params`
    draws them and for its reason (at 0.02 throughout every late position
    is one direction): matrices normal 0.02, the embedding normal 1, the
    matrices that write into the residual stream (every down projection,
    the shared experts' too) normal 0.02 / sqrt(2 L) over the L layers
    held; and, so that attention over thousands of rows is NOT averaged
    away and a wrong cache row moves the tokens served, `q` normal 0.02 x
    `SEEDED_Q_GAIN` and attention's `o` normal 0.02 / sqrt(2 L) x
    `SEEDED_O_GAIN`. The router's correction bias normal 0.01 (as the
    hybrid's), norm weights 1, both float32. No matrix reads a one-signed
    activation (a SwiGLU's product is signed), so none is centred."""
    dt = cfg.compute_dtype
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    nh, lat, rope = (cfg.num_attention_heads, cfg.kv_lora_rank,
                     cfg.qk_rope_head_dim)
    n = cfg.experts_held[1]
    shared = cfg.n_shared_experts * f
    keys = iter(jax.random.split(key, 2 + 13 * cfg.num_hidden_layers))
    into_residual = 0.02 / (2 * cfg.num_hidden_layers) ** 0.5

    def mat(*shape, std=0.02, dtype=dt):
        return std * jax.random.normal(next(keys), shape, dtype)

    def ones(m):
        return jnp.ones((m,), jnp.float32)

    layers = []
    for li in range(cfg.num_hidden_layers):
        layer = dict(
            norm1=ones(h),
            q=mat(h, nh * cfg.qk_head_dim, std=0.02 * SEEDED_Q_GAIN),
            kva=mat(h, lat + rope), kv_norm=ones(lat),
            k_up=mat(lat, nh * cfg.qk_nope_head_dim),
            v_up=mat(lat, nh * cfg.v_head_dim),
            o=mat(nh * cfg.v_head_dim, h,
                  std=into_residual * SEEDED_O_GAIN), norm2=ones(h))
        if cfg.is_dense(li):
            layer.update(gate=mat(h, cfg.intermediate_size),
                         up=mat(h, cfg.intermediate_size),
                         down=mat(cfg.intermediate_size, h,
                                  std=into_residual))
        else:
            layer.update(
                router=mat(h, cfg.n_routed_experts),
                router_bias=mat(cfg.n_routed_experts, std=0.01,
                                dtype=jnp.float32),
                w_gate=mat(n, h, f), w_up=mat(n, h, f),
                w_down=mat(n, f, h, std=into_residual),
                s_gate=mat(h, shared), s_up=mat(h, shared),
                s_down=mat(shared, h, std=into_residual))
        layers.append(layer)
    return {"embed": mat(cfg.vocab_size, h, std=1.0), "layers": layers,
            "norm_f": ones(h), "head": mat(h, cfg.vocab_size)}


# -- pieces -----------------------------------------------------------------
def rope_tables(cfg, positions):
    """(cos, sin) (T, R) float32 at `positions` (T,): lanes 2i and 2i + 1
    both hold angle i's cosine, and its sine with the sign each lane's
    partner takes (-, +)."""
    half = cfg.qk_rope_head_dim // 2
    ang = positions.astype(jnp.float32)[:, None] * cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    sign = jnp.tile(jnp.array([-1.0, 1.0], jnp.float32), half)
    return (jnp.repeat(jnp.cos(ang), 2, axis=-1),
            jnp.repeat(jnp.sin(ang), 2, axis=-1) * sign)


def rotate(x, table):
    """Rotary in interleaved pairs over heads `x` (T, heads, R): lane 2i
    turns with lane 2i + 1 by the table's angle i, in float32."""
    cos, sin = (a[:, None, :] for a in table)
    x32 = x.astype(jnp.float32)
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, jnp.roll(x32, -1, axis=-1),
                        jnp.roll(x32, 1, axis=-1))
    return (x32 * cos + partner * sin).astype(x.dtype)


def attention_inputs(cfg, layer, u, tables):
    """What a layer's attention reads of tokens `u` (T, H) at the positions
    behind `tables`: q_nope (T, heads, N), q_rope (T, heads, R) rotated,
    and the decode state c (T, L) normed, kr (T, R) rotated."""
    dt = u.dtype
    t = u.shape[0]
    lat = cfg.kv_lora_rank
    with jax.named_scope("qkv"):
        q = (u @ layer["q"].astype(dt)).reshape(t, -1, cfg.qk_head_dim)
        kva = u @ layer["kva"].astype(dt)
        c = rms_norm(kva[:, :lat], layer["kv_norm"], cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        q_rope = rotate(q[..., cfg.qk_nope_head_dim:], tables)
        kr = rotate(kva[:, None, lat:], tables)[:, 0]
    return q[..., :cfg.qk_nope_head_dim], q_rope, c, kr


def causal_attention(cfg, q_nope, q_rope, c, kr, layer, impl="auto",
                     plen=None):
    """The expanded form over one sequence: keys and values decompressed
    from the latent (scope `expand`), causal softmax a head (scope
    `flash_prefill`). (T, heads·V) out. impl 'auto' (the Pallas kernel on a
    TPU, XLA elsewhere), 'pallas' or 'dense'. With `plen`, the sequence's
    real rows, the kernel runs the tile pairs up to it alone
    (`flash_attention(..., lengths=)`): the rows of the q tiles past it
    come back as zeros, every other row as without it."""
    dt = c.dtype
    t, nh, n = q_nope.shape
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "dense"
    with jax.named_scope("expand"):
        k_nope = (c @ layer["k_up"].astype(dt)).reshape(t, nh, n)
        v = (c @ layer["v_up"].astype(dt)).reshape(t, nh, -1)
    with jax.named_scope("flash_prefill"):
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, None, :], q_rope.shape)],
            axis=-1)
        if impl == "pallas":
            block = min(PREFILL_BLOCK, t)
            out = flash_attention(
                *(a.transpose(1, 0, 2)[None] for a in (q, k, v)),
                causal=True, block_q=block, block_k=block, native=True,
                lengths=real_rows(plen))
            return out[0].transpose(1, 0, 2).reshape(t, -1)
        if impl != "dense":
            raise ValueError(f"unknown attention impl {impl!r}; expected "
                             f"'auto', 'pallas' or 'dense'")
        s = jnp.einsum("qhd,khd->hqk", q, k,
                       preferred_element_type=jnp.float32) * cfg.attn_scale
        seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p.astype(dt), v,
                          preferred_element_type=jnp.float32
                          ).astype(dt).reshape(t, -1)


def absorbed_attention(cfg, layer, q_nope, q_rope, leaf, lengths,
                       impl="auto"):
    """The absorbed form for one token a slot against a latent leaf
    (`kernels/mla_attention.py`) whose rows 0..lengths - 1 are in use, the
    token's own among them: (S, heads·V) out."""
    dt = q_nope.dtype
    s, nh, n = q_nope.shape
    lat = cfg.kv_lora_rank
    with jax.named_scope("absorb"):
        q_lat = jnp.einsum("shn,lhn->shl", q_nope,
                           layer["k_up"].astype(dt).reshape(lat, nh, n),
                           preferred_element_type=jnp.float32).astype(dt)
    o_lat = mla_attention_decode(q_lat, q_rope, leaf, lengths,
                                 cfg.attn_scale, impl=impl)
    with jax.named_scope("unabsorb"):
        return jnp.einsum("shl,lhv->shv", o_lat,
                          layer["v_up"].astype(dt).reshape(lat, nh, -1),
                          preferred_element_type=jnp.float32
                          ).astype(dt).reshape(s, -1)


def feed_forward(cfg, li, layer, x):
    """A layer's second half over tokens `x` (T, H): `x + ffn(RMSNorm(x))`,
    dense below `first_k_dense_replace` (scope `ffn`), experts after (scope
    `moe`). Returns (y, the expert layer's counts or None)."""
    g = rms_norm(x, layer["norm2"], cfg.rms_norm_eps)
    if cfg.is_dense(li):
        with jax.named_scope("ffn"):
            return x + swiglu(g, layer["gate"], layer["up"],
                              layer["down"]), None
    with jax.named_scope("moe"):
        out, counts = moe(cfg, layer, g)
    return x + out.astype(x.dtype), counts


# -- the whole model --------------------------------------------------------
def apply_layer(cfg, li, layer, x, tables, impl="auto", plen=None):
    """Layer `li` over a whole sequence `x` (T, H), its first `plen` rows
    real (all by default), in the expanded form; returns (y, the layer's
    decode state (c, kr))."""
    with jax.named_scope("norm"):
        u = rms_norm(x, layer["norm1"], cfg.rms_norm_eps)
    with jax.named_scope("attn"):
        q_nope, q_rope, c, kr = attention_inputs(cfg, layer, u, tables)
        ctx = causal_attention(cfg, q_nope, q_rope, c, kr, layer, impl,
                               plen)
        with jax.named_scope("proj"):
            x = x + ctx @ layer["o"].astype(x.dtype)
    return feed_forward(cfg, li, layer, x)[0], (c, kr)


def encode(cfg, params, ids, impl="auto", plen=None):
    """The full-sequence forward over ONE sequence `ids` (T,), of which the
    first `plen` (all by default) are real, up to the last layer's output
    (T, H), and every layer's decode state: the rows (c (T, L), kr (T, R))
    a latent cache holds. Rows at or past `plen` are padding: what they
    hold is never read for a real row (`causal_attention`)."""
    with jax.named_scope("rope"):
        tables = rope_tables(cfg, jnp.arange(ids.shape[0]))
    with jax.named_scope("embed"):
        x = embed(cfg, params, ids)
    states = []
    for li, layer in enumerate(params["layers"]):
        with jax.named_scope(f"layer{li}"):
            x, state = apply_layer(cfg, li, layer, x, tables, impl, plen)
        states.append(state)
    return x, states


# -- decode (`generation/decode.py`'s `MLADecoder`) ---------------------------
def decode_leaves(cfg):
    """ONE latent leaf a layer, `(C / 2, 2·(L + R))` at an even rung of C,
    row r holding positions 2r and 2r + 1 as `[c, c', kr, kr']`
    (`mla_attention.pack_latent`)."""
    leaf = Leaf("kv", (2 * cfg.latent_width,), cfg.compute_dtype, per_row=2)
    return [(leaf,)] * cfg.num_hidden_layers


def decode_context(cfg, pos, leaves, counts):
    """The rows in use at `pos` (S,), 0..pos (the current write included; a
    lane frozen at the end of its rung, pos == C, keeps all of them), the
    rows the kernel fetches for them (rounded up to its tile) and the
    rotary tables; the expert layers' tally from zero."""
    leaf = leaves["kv"][0]
    c = 2 * leaf.shape[1]
    in_use = jnp.minimum(pos + 1, c)                        # (S,)
    tile = latent_tile_positions(c, cfg.kv_lora_rank, leaf.dtype)
    read = (-(-in_use // tile) * tile).sum()
    with jax.named_scope("rope"):
        tables = rope_tables(cfg, pos)
    return dict(pos=pos, in_use=in_use, read=read, tables=tables,
                counts=counts), jnp.zeros((3,), jnp.int32)


def decode_layer(cfg, li, layer, x, ctx, state, tally, impl="auto"):
    """Layer `li` for ONE token a slot, `x` (S, H), in the absorbed form
    against its latent leaf `state` = (leaf,): the token's row written at
    its position, the rows in use attended, each read once as key and as
    value; then the feed-forward."""
    (leaf,) = state
    with jax.named_scope(f"layer{li}"):
        with jax.named_scope("norm"):
            u = rms_norm(x, layer["norm1"], cfg.rms_norm_eps)
        with jax.named_scope("attn"):
            q_nope, q_rope, c, kr = attention_inputs(cfg, layer, u,
                                                     ctx["tables"])
            with jax.named_scope("kv_write"):
                leaf = write_latent_row(leaf, ctx["pos"], c, kr)
            out = absorbed_attention(cfg, layer, q_nope, q_rope, leaf,
                                     ctx["in_use"], impl)
            with jax.named_scope("proj"):
                x = x + out @ layer["o"].astype(x.dtype)
        y, counts = feed_forward(cfg, li, layer, x)
        if counts is not None:
            tally = tally + counts
    return y, (leaf,), tally


def decode_counts(cfg, ctx, tally):
    """The expert layers' tally, then the rows attended (what the
    mathematics needs) and the rows the kernel fetched, slots and layers
    summed."""
    return ctx["counts"] + jnp.concatenate([
        tally, cfg.num_hidden_layers * jnp.stack(
            [ctx["in_use"].sum(), ctx["read"]]).astype(jnp.int32)])


def encode_prompt(cfg, params, prompt, plen, impl="auto"):
    return encode(cfg, params, prompt, impl=impl, plen=plen)


def cache_parts(cfg, li, state, plen, leaves):
    return (pack_latent(*state),)


def prefill_windows(cfg):
    return [None] * cfg.num_hidden_layers


def forward(cfg, params, ids, impl="auto"):
    """Next-token logits at every position of `ids` (T,) or (B, T), the
    rows of a batch one after the other."""
    if ids.ndim == 1:
        return logits(cfg, params, encode(cfg, params, ids, impl)[0])
    return jnp.stack([forward(cfg, params, row, impl) for row in ids])
