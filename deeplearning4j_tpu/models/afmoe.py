"""Arcee's AFMoE language model (`model_type` afmoe, as Trinity-Mini
publishes it): grouped-query attention whose layers are of two kinds,
SLIDING (a window of the last `sliding_window` positions, rotary) and FULL
(every earlier position, no positional encoding at all), an output gate on
attention, sandwich norms, two leading dense layers, then sigmoid-routed
SwiGLU experts beside one shared expert.

The fifth model family beside `models/bert.py`, `models/nemotron_h.py`,
`models/keye_vl.py` and `models/deepseek_v3.py`. Embedding `x_0 = E[id] *
sqrt(H)` under `mup_enabled`. For a layer input `x` (T, H) at positions t:

- `u = RMSNorm_in(x)`; `q = u W_q` (Hq heads of D), `k = u W_k`, `v = u
  W_v` (Hkv heads), `g = u W_gate` (Hq·D); q and k RMSNorm over each
  head's D lanes (one weight of D shared by the heads).
- a sliding layer (`layer_types[i] == "sliding_attention"`, all but every
  `global_attn_every_n_layers`-th) rotates q and k by RoPE in the
  rotate-half layout (lane j with lane j + D/2, angle `t theta^(-2j/D)`)
  and attends `t - sliding_window < s <= t`; a full layer rotates nothing
  and attends `s <= t`. Query head h reads KV head h // (Hq / Hkv), float32
  softmax of `q . k / sqrt(D)`.
- `a = concat_h(o_h) * sigmoid(g)`; `h = x + RMSNorm_post_attn(a W_o)`.
- `m = RMSNorm_pre_mlp(h)`; below `num_dense_layers` a dense SwiGLU, after
  it the expert layer `decoder_common.moe` (`sigmoid(m W_r)` over ALL
  experts in float32, the top-k of `s + b` with `b` for the CHOICE only,
  weights `s_e / sum of the chosen s` times `route_scale`, the experts HELD
  here through `parallel.moe.routed_experts`, the shared expert on every
  token); `y = h + RMSNorm_post_mlp(f)`.
- after the last layer, RMSNorm and an untied head.

A position's decode state a layer is its k (normed, and rotated on a
sliding layer) and v. `generation/decode.py`'s `AfmoeDecoder` keeps a
sliding layer's in a ring of `sliding_window` rows and a full layer's in
rows that grow with the cache rung (`decode_leaves`).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels.flash_attention import (
    flash_attention, flash_attention_decode)
# (RMSNorm, the final norm and head, and the sigmoid-routed expert layer
# with its shared SwiGLU are every served decoder's)
from deeplearning4j_tpu.models.decoder_common import (  # noqa: F401
    Leaf, expert_runs, logits, moe, real_rows, rms_norm, rope_tables, rotate,
    swiglu, write_rows)

#: rows of queries and of keys a grid step of a prompt's attention takes,
#: full layers (causal) and sliding layers (the band) alike. The scores of
#: a 1024 x 1024 tile are 4 MB of float32
PREFILL_BLOCK = 1024

_KINDS = ("sliding_attention", "full_attention")


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int          # a dense layer's feed-forward
    moe_intermediate_size: int      # an expert's, and the shared expert's
    num_experts: int                # the router's outputs: ALL experts
    experts_held: tuple             # (first, count) of those held here
    num_experts_per_tok: int
    num_shared_experts: int
    num_dense_layers: int
    sliding_window: int
    global_attn_every_n_layers: int
    route_scale: float = 1.0
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    dtype: str = "float32"          # compute and weight dtype

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        if self.head_dim % 2 or self.sliding_window < 1 \
                or self.global_attn_every_n_layers < 1:
            raise ValueError("rotary halves need an even head_dim; the "
                             "window and the layer period are positive")
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.num_experts}")

    @classmethod
    def from_dict(cls, d, **over):
        """From a `config.json`'s keys (`model_type` afmoe). The file's
        `num_experts` is the router's width; `experts_held` defaults to all
        of them. What this module does not compute is refused, not
        ignored: groups of experts, a softmax router, unnormalised routing
        weights, rope scaling, and `layer_types` other than every
        `global_attn_every_n_layers`-th layer full."""
        wants = dict(score_func="sigmoid", route_norm=True, n_group=1,
                     topk_group=1, num_expert_groups=1, num_limited_groups=1,
                     rope_scaling=None, hidden_act="silu",
                     tie_word_embeddings=False)
        for key, want in wants.items():
            if d.get(key, want) != want:
                raise ValueError(f"{key} = {d[key]!r}: only {want!r} is "
                                 f"computed here")
        every = int(d["global_attn_every_n_layers"])
        types = list(d.get("layer_types") or [])
        if len(types) < int(d["num_hidden_layers"]) or types != [
                _KINDS[(i + 1) % every == 0] for i in range(len(types))]:
            raise ValueError(f"layer_types must name every layer, the "
                             f"{every}th full and the rest sliding")
        kw = {k: int(d[k]) for k in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok", "num_shared_experts", "num_dense_layers",
            "sliding_window")}
        kw.update(global_attn_every_n_layers=every,
                  route_scale=float(d["route_scale"]),
                  rope_theta=float(d["rope_theta"]),
                  rms_norm_eps=float(d["rms_norm_eps"]),
                  mup_enabled=bool(d.get("mup_enabled", False)),
                  experts_held=(0, int(d["num_experts"])))
        kw.update(over)
        kw["experts_held"] = tuple(int(v) for v in kw["experts_held"])
        return cls(**kw)

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @property
    def q_width(self):
        return self.num_attention_heads * self.head_dim

    @property
    def kv_width(self):
        return self.num_key_value_heads * self.head_dim

    @property
    def routed_scaling_factor(self):
        """`route_scale` under the name `decoder_common.moe` reads."""
        return self.route_scale

    @property
    def embed_scale(self):
        return self.hidden_size ** 0.5 if self.mup_enabled else 1.0

    def is_sliding(self, li):
        return (li + 1) % self.global_attn_every_n_layers != 0

    def is_dense(self, li):
        return li < self.num_dense_layers


# -- parameters -------------------------------------------------------------
#: the q norm's weight in the seeded draw. q and k are RMS-normed a head,
#: so a larger W_q changes nothing: the scores' spread is the norms'
#: weights' (at 1 and 1 a score spreads by about 1 over a prompt's rows,
#: and attention over thousands of them nears their mean). At 2.5, on the
#: chip at the published widths (`PERF.md`, section 6): 414 of 512
#: late positions of a 4096-token prompt have an argmax of their own, and
#: the sliding layers attending everything, the full layers rotated or
#: the gate left out move those logits by 0.87, 0.39 and 0.70 rms
SEEDED_Q_NORM = 2.5


def init_params(cfg, key):
    """Seeded weights, in the compute dtype: matrices normal 0.02, the
    matrices that write into the residual stream (`o`, every down
    projection) normal 0.02 / sqrt(2 L) over the L layers held (their
    output is RMS-normed before it is added, so their scale only sets the
    norms' input), the embedding normal 1 / sqrt(H) so that the muP factor
    sqrt(H) gives `x_0` an RMS of 1, the q norm's weight `SEEDED_Q_NORM`
    and every other norm weight 1, the router's correction bias normal
    0.01 float32. No matrix reads a one-signed activation (a SwiGLU's
    product and the gated values are signed), so none is centred."""
    dt = cfg.compute_dtype
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    n = cfg.experts_held[1]
    shared = cfg.num_shared_experts * f
    keys = iter(jax.random.split(key, 2 + 12 * cfg.num_hidden_layers))
    into_residual = 0.02 / (2 * cfg.num_hidden_layers) ** 0.5

    def mat(*shape, std=0.02, dtype=dt):
        return std * jax.random.normal(next(keys), shape, dtype)

    def ones(m, value=1.0):
        return jnp.full((m,), value, jnp.float32)

    layers = []
    for li in range(cfg.num_hidden_layers):
        layer = dict(
            norm_in=ones(h), q=mat(h, cfg.q_width), k=mat(h, cfg.kv_width),
            v=mat(h, cfg.kv_width), gate=mat(h, cfg.q_width),
            o=mat(cfg.q_width, h, std=into_residual),
            q_norm=ones(cfg.head_dim, SEEDED_Q_NORM),
            k_norm=ones(cfg.head_dim), norm_post_attn=ones(h),
            norm_pre_mlp=ones(h), norm_post_mlp=ones(h))
        if cfg.is_dense(li):
            layer.update(d_gate=mat(h, cfg.intermediate_size),
                         d_up=mat(h, cfg.intermediate_size),
                         d_down=mat(cfg.intermediate_size, h,
                                    std=into_residual))
        else:
            layer.update(
                router=mat(h, cfg.num_experts),
                router_bias=mat(cfg.num_experts, std=0.01,
                                dtype=jnp.float32),
                w_gate=mat(n, h, f), w_up=mat(n, h, f),
                w_down=mat(n, f, h, std=into_residual),
                s_gate=mat(h, shared), s_up=mat(h, shared),
                s_down=mat(shared, h, std=into_residual))
        layers.append(layer)
    return {"embed": mat(cfg.vocab_size, h, std=1.0 / cfg.embed_scale),
            "layers": layers, "norm_f": ones(h),
            "head": mat(h, cfg.vocab_size)}


# -- pieces -----------------------------------------------------------------
def embed(cfg, params, ids):
    """`E[id] * sqrt(H)` under `mup_enabled`, in the compute dtype."""
    x = jnp.take(params["embed"], ids, axis=0)
    return (x * cfg.embed_scale).astype(x.dtype)


def attention_inputs(cfg, li, layer, u, tables):
    """What layer `li`'s attention reads of tokens `u` (T, H) at the
    positions behind `tables`: q (T, Hq, D), the cache rows k, v (T,
    Hkv·D), and the gate's input g (T, Hq·D). q and k normed a head, and
    rotated on a sliding layer (scope `rope`) and on no other."""
    dt = u.dtype
    t = u.shape[0]
    d, eps = cfg.head_dim, cfg.rms_norm_eps
    with jax.named_scope("qkv"):
        q = rms_norm((u @ layer["q"].astype(dt)).reshape(t, -1, d),
                     layer["q_norm"], eps)
        k = rms_norm((u @ layer["k"].astype(dt)).reshape(t, -1, d),
                     layer["k_norm"], eps)
        v = u @ layer["v"].astype(dt)
        g = u @ layer["gate"].astype(dt)
    if cfg.is_sliding(li):
        with jax.named_scope("rope"):
            q, k = rotate(q, tables), rotate(k, tables)
    return q, k.reshape(t, -1), v, g


def attention_scope(cfg, li):
    """The scope that wraps layer `li`'s attention kernel call alone:
    `window` on a sliding layer, `global` on a full one."""
    return "window" if cfg.is_sliding(li) else "global"


def sequence_attention(cfg, li, q, k, v, impl="auto", plen=None):
    """A whole sequence's attention in layer `li`: q (T, Hq, D), k, v (T,
    Hkv·D) -> (T, Hq·D). A sliding layer's band through `flash_attention(
    ..., window=)` (its K/V tiles below the band neither fetched nor
    computed), a full layer's causal triangle; both in scope
    `window`/`global` -> `flash_prefill`. The kernel takes every query
    head's own K/V rows: the KV heads are repeated to the query heads
    first. impl 'auto' (the kernels on a TPU, XLA elsewhere), 'pallas' or
    'dense'. With `plen`, the sequence's real rows, either kernel runs the
    tile pairs up to it alone (`flash_attention(..., lengths=)`): the rows
    of the q tiles past it come back as zeros, every other row as without
    it."""
    t, nh, d = q.shape
    hkv = cfg.num_key_value_heads
    window = cfg.sliding_window if cfg.is_sliding(li) else None
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "dense"
    with jax.named_scope(attention_scope(cfg, li)), \
            jax.named_scope("flash_prefill"):
        if impl == "pallas":
            block = min(PREFILL_BLOCK, t)
            kh, vh = (jnp.repeat(a.reshape(t, hkv, d), nh // hkv, axis=1)
                      for a in (k, v))
            out = flash_attention(
                *(a.transpose(1, 0, 2)[None] for a in (q, kh, vh)),
                causal=True, block_q=block, block_k=block, native=True,
                window=window, lengths=real_rows(plen))
            return out[0].transpose(1, 0, 2).reshape(t, -1)
        if impl != "dense":
            raise ValueError(f"unknown attention impl {impl!r}; expected "
                             f"'auto', 'pallas' or 'dense'")
        qg = q.reshape(t, hkv, nh // hkv, d)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k.reshape(t, hkv, d),
                       preferred_element_type=jnp.float32) * d ** -0.5
        tq, tk = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = tk <= tq
        if window is not None:
            seen &= tk > tq - window
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype),
                          v.reshape(t, hkv, d),
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype).reshape(t, -1)


def attention_output(cfg, layer, x, ctx, g):
    """`x + RMSNorm_post_attn((ctx * sigmoid(g)) W_o)`: the gate (scope
    `gate`), the output projection and its norm (scope `proj`)."""
    dt = x.dtype
    with jax.named_scope("gate"):
        a = (ctx.astype(jnp.float32)
             * jax.nn.sigmoid(g.astype(jnp.float32))).astype(dt)
    with jax.named_scope("proj"):
        return x + rms_norm(a @ layer["o"].astype(dt),
                            layer["norm_post_attn"], cfg.rms_norm_eps)


def feed_forward(cfg, li, layer, x):
    """A layer's second half over tokens `x` (T, H): `x +
    RMSNorm_post_mlp(ffn(RMSNorm_pre_mlp(x)))`, a dense SwiGLU below
    `num_dense_layers` (scope `ffn`), the expert layer after (scope `moe`).
    Returns (y, the expert layer's counts or None)."""
    eps = cfg.rms_norm_eps
    m = rms_norm(x, layer["norm_pre_mlp"], eps)
    if cfg.is_dense(li):
        with jax.named_scope("ffn"):
            f = swiglu(m, layer["d_gate"], layer["d_up"], layer["d_down"])
            return x + rms_norm(f, layer["norm_post_mlp"], eps), None
    with jax.named_scope("moe"):
        f, counts = moe(cfg, layer, m)
        return x + rms_norm(f, layer["norm_post_mlp"], eps).astype(
            x.dtype), counts


# -- the whole model --------------------------------------------------------
def apply_layer(cfg, li, layer, x, tables, impl="auto", plen=None):
    """Layer `li` over a whole sequence `x` (T, H), its first `plen` rows
    real (all by default); returns (y, the layer's decode state (k, v),
    each (T, Hkv·D))."""
    with jax.named_scope("norm"):
        u = rms_norm(x, layer["norm_in"], cfg.rms_norm_eps)
    with jax.named_scope("attn"):
        q, k, v, g = attention_inputs(cfg, li, layer, u, tables)
        ctx = sequence_attention(cfg, li, q, k, v, impl, plen)
        x = attention_output(cfg, layer, x, ctx, g)
    return feed_forward(cfg, li, layer, x)[0], (k, v)


def encode(cfg, params, ids, impl="auto", plen=None):
    """The full-sequence forward over ONE sequence `ids` (T,), of which the
    first `plen` (all by default) are real, up to the last layer's output
    (T, H), and every layer's decode state: the k and v rows (T, Hkv·D) a
    cache holds. Rows at or past `plen` are padding: what they hold is
    never read for a real row (`sequence_attention`)."""
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


# -- decode (`generation/decode.py`'s `AfmoeDecoder`) --------------------------
def decode_leaves(cfg):
    """A K and a V leaf a layer, rows major: a sliding layer's a RING of
    `min(W, C)` rows at a rung of C (position p at row p mod W), a full
    layer's C rows."""
    def kv(li):
        cap = cfg.sliding_window if cfg.is_sliding(li) else None
        return tuple(Leaf(name, (cfg.kv_width,), cfg.compute_dtype, cap=cap)
                     for name in ("k", "v"))
    return [kv(li) for li in range(cfg.num_hidden_layers)]


def decode_context(cfg, pos, leaves, counts):
    """The rotary tables at `pos` (S,); the tally (the expert layers'
    counts, the rows in use of the sliding and of the full layers) from
    zero."""
    with jax.named_scope("rope"):
        tables = rope_tables(cfg, pos)
    return dict(pos=pos, tables=tables, counts=counts), (
        jnp.zeros((3,), jnp.int32), jnp.zeros((2,), jnp.int32))


def decode_layer(cfg, li, layer, x, ctx, state, tally, impl="auto"):
    """Layer `li` for ONE token a slot, `x` (S, H), against its leaves
    `state` = (k, v) `(S, R, Hkv·D)`: the token's rows written at its
    position, or at the position mod W on a ring, and the rows in use,
    `min(pos + 1, R)`, attended in scope `window`/`global` (a full ring
    whole: a softmax does not care in which order rows come); then the
    feed-forward."""
    pos = ctx["pos"]
    s = x.shape[0]
    kc, vc = state
    sliding = cfg.is_sliding(li)
    # rows 0..pos are in use (the current write included); a lane frozen
    # at the end of its rung (pos == C) keeps all of them
    lengths = jnp.minimum(pos + 1, kc.shape[1])                 # (S,)
    write = pos % cfg.sliding_window if sliding else pos
    with jax.named_scope(f"layer{li}"):
        with jax.named_scope("norm"):
            u = rms_norm(x, layer["norm_in"], cfg.rms_norm_eps)
        with jax.named_scope("attn"):
            q, k, v, g = attention_inputs(cfg, li, layer, u, ctx["tables"])
            with jax.named_scope("kv_write"):
                kc, vc = write_rows((kc, vc), jnp.arange(s), write, (k, v))
            with jax.named_scope(attention_scope(cfg, li)):
                rows = jnp.arange(kc.shape[1])[None, :] < lengths[:, None]
                out = flash_attention_decode(q, kc, vc, rows, impl=impl,
                                             lengths=lengths)
            x = attention_output(cfg, layer, x, out.reshape(s, -1), g)
        y, counts = feed_forward(cfg, li, layer, x)
    counted, in_use = tally
    in_use = in_use.at[0 if sliding else 1].add(lengths.sum())
    if counts is not None:
        counted = counted + counts
    return y, (kc, vc), (counted, in_use)


def decode_counts(cfg, ctx, tally):
    return ctx["counts"] + jnp.concatenate(tally)


def encode_prompt(cfg, params, prompt, plen, impl="auto"):
    return encode(cfg, params, prompt, impl=impl, plen=plen)


def cache_parts(cfg, li, state, plen, leaves):
    """A full layer's rows 0..P-1; a sliding layer's ring row r the
    prompt's position `plen - 1 - ((plen - 1 - r) mod W)`, the last W REAL
    positions (a row whose position would be negative is written before it
    is read)."""
    if not cfg.is_sliding(li):
        return state
    r = jnp.arange(leaves[0].shape[1])
    at = plen - 1 - (plen - 1 - r) % cfg.sliding_window
    return tuple(jnp.take(part, jnp.maximum(at, 0), axis=0)
                 for part in state)


def prefill_windows(cfg):
    return [cfg.sliding_window if cfg.is_sliding(li) else None
            for li in range(cfg.num_hidden_layers)]


def forward(cfg, params, ids, impl="auto"):
    """Next-token logits at every position of `ids` (T,) or (B, T), the
    rows of a batch one after the other."""
    if ids.ndim == 1:
        return logits(cfg, params, encode(cfg, params, ids, impl)[0])
    return jnp.stack([forward(cfg, params, row, impl) for row in ids])
