"""Keye-VL-2.0 style language model: grouped-query attention under a learned
sparse-attention indexer, M-RoPE in three position streams, and a
softmax-routed layer of gated experts in every block.

The third model family beside `models/bert.py` and `models/nemotron_h.py`.
Every layer is pre-norm, `h = x + attn(RMSNorm(x))`, `y = h + moe(RMSNorm(
h))`; after the last, RMSNorm and an untied head. No biases. For a layer
input `u = RMSNorm(x)` (T, H) and position streams `p` (3, T):

- attention: `q = u W_q` (Hq heads of D), `k = u W_k`, `v = u W_v` (Hkv
  heads); q and k RMSNorm over each head's D lanes; M-RoPE in the
  rotate-half layout: frequency i of D/2 turns by `p[c(i), t] theta^(-2i /
  D)`, the stream c(i) given by `mrope_section` (16 | 24 | 24 of 64).
- the indexer (DeepSeek sparse attention): `qI = u W_qI` (J heads of Di),
  ONE key head `kI = LayerNorm(u W_kI)`, head weights `w = u W_w`; rotary
  over all Di lanes at stream 0; `I[t, s] = (J Di)^(-1/2) sum_j w[t, j]
  relu(qI[t, j] . kI[s])` for s <= t, float32 sums. Query t attends the
  `min(topk, t + 1)` positions of largest `I[t, s]` (ties to the lower s)
  and no others: `kernels/selection.py` finds them exactly, without a sort.
- experts: `softmax(g W_r)` over ALL experts in float32, the top-k
  renormalised (`norm_topk_prob`), expert e `(silu(g W_g^e) * (g W_u^e))
  W_d^e` through `parallel.moe.routed_experts` for the experts HELD here.

`encode` is the full-sequence forward (prefill's arithmetic) and hands back
each layer's decode state: K, V and index-key rows. `generation/decode.py`'s
`KeyeDecoder` serves it from the decode state declared here
(`decode_leaves`) and the step's and the prefill's hooks. The vision tower is not modelled; the three
position streams are, and text gives all three the token's index.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.kernels.flash_attention import (
    decode_tile_rows, flash_attention_decode, flash_attention_selected)
from deeplearning4j_tpu.kernels.indexer import (index_scores,
                                                index_scores_decode,
                                                pack_rows, write_packed_row)
from deeplearning4j_tpu.kernels.selection import compact_indices, top_k_mask
from deeplearning4j_tpu.models.decoder_common import (  # noqa: F401
    MOE_CHUNK, Leaf, embed, logits, rms_norm, rotate, slot_index,
    write_rows)
from deeplearning4j_tpu.parallel.moe import routed_calls, routed_experts

#: query rows of a prefill whose index scores and selection exist at once
#: ((Q_BLOCK, T) float32 scores and int8 selection: 268 + 67 MB at 16384)
Q_BLOCK = 4096
#: rows whose threshold one `top_k_mask` call finds (their scores' unsigned
#: image is 8 MB at 16384 columns, which 32 counting passes can keep near)
SELECT_ROWS = 128
#: a decode step attends its kept rows where they lie while the rung holds
#: at most this many times `topk` rows, and gathers them past it. Two rates
#: measured on a v5e (`PERF.md` §6; K and V rows of 1 KB): gathered, a
#: kept row costs 38.9 ns (XLA's row gather 2 x 15.2 ns, the index
#: arithmetic around it 5.8 and the kernel over the gathered rung 2.7),
#: whatever the context; read in place, a row in use costs 2.9 ns. In place
#: wins while a slot has fewer than 38.9 / 2.9 = 13.4 x `topk` rows in use,
#: which the rung bounds: 12 leaves a tenth of room
IN_PLACE_RUNGS = 12


@dataclass(frozen=True)
class KeyeVLConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int                # the router's outputs: ALL experts
    experts_held: tuple             # (first, count) of those held here
    num_experts_per_tok: int
    moe_intermediate_size: int
    indexer_num_heads: int
    indexer_head_dim: int
    indexer_topk: int
    mrope_section: tuple            # frequencies a position stream turns
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    dtype: str = "float32"          # compute and weight dtype

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        if len(self.mrope_section) != 3 \
                or sum(self.mrope_section) * 2 != self.head_dim:
            raise ValueError(
                f"mrope_section {self.mrope_section} must give three "
                f"streams {self.head_dim // 2} frequencies between them")
        if self.indexer_head_dim % 2 or self.indexer_topk < 1:
            raise ValueError("the indexer needs an even head width and "
                             "topk >= 1")
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.num_experts}")

    @classmethod
    def from_dict(cls, d, **over):
        """From a `config.json`'s keys (`model_type` KeyeVL2). The file's
        `num_experts` is the router's width; `experts_held` defaults to all
        of them."""
        sa = d["sa_config"]
        if int(sa.get("indexer_num_kv_heads", 1)) != 1:
            raise ValueError("the indexer reads ONE key head")
        if not d.get("norm_topk_prob", True) or d.get("mlp_only_layers") \
                or int(d.get("decoder_sparse_step", 1)) != 1:
            raise ValueError("every layer is an expert layer whose top-k "
                             "weights are renormalised")
        kw = {k: int(d[k]) for k in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts", "num_experts_per_tok", "moe_intermediate_size")}
        kw.update(
            indexer_num_heads=int(sa["indexer_num_heads"]),
            indexer_head_dim=int(sa["indexer_head_dim"]),
            indexer_topk=int(sa["topk"]),
            mrope_section=d["rope_scaling"]["mrope_section"],
            rope_theta=float(d["rope_theta"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            experts_held=(0, int(d["num_experts"])))
        kw.update(over)
        kw["experts_held"] = tuple(int(v) for v in kw["experts_held"])
        kw["mrope_section"] = tuple(int(v) for v in kw["mrope_section"])
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
    def index_scale(self):
        return (self.indexer_num_heads * self.indexer_head_dim) ** -0.5


# -- parameters -------------------------------------------------------------
def init_params(cfg, key):
    """Seeded weights, in the compute dtype: matrices normal 0.02, but the
    embedding normal 1 (the default of the source's framework for an
    embedding table) and the two matrices that write into the residual
    stream, attention's `o` and an expert's `w_down`, normal 0.02 / sqrt(2
    L) over the L layers held (GPT-2's scaled initialisation). Norm weights
    1 and the index key's LayerNorm bias 0, float32.

    Why not 0.02 throughout. Attention averages the value rows of up to
    `topk` positions: what the positions have in common passes through `v`
    and `o` whole (a gain of 1.16 at the published widths with both at
    0.02), what tells them apart is divided by the root of the rows
    averaged. With a 0.02 embedding the first layer's output already
    outweighs the token's own row, each layer multiplies the common part by
    1.5, and after 8 layers every position is ONE direction (cosine 0.99
    between late positions; 2 distinct argmax tokens in 2048 positions:
    CPU, published widths): the served streams repeat one token, and a
    comparison of logits compares nothing. Trained weights carry no such
    vector. Drawn as above the cosine is 0.003, 504 of 512 late positions
    have an argmax of their own, and the indexer's selection still decides
    one argmax in seven (`PERF.md`, Findings, PR 35). No matrix here reads
    a one-signed activation (a gated expert's product and the values are
    signed), so nothing is centred as `nemotron_h.init_params` centres."""
    dt = cfg.compute_dtype
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    n = cfg.experts_held[1]
    nj, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    keys = iter(jax.random.split(key, 2 + 11 * cfg.num_hidden_layers))
    into_residual = 0.02 / (2 * cfg.num_hidden_layers) ** 0.5

    def mat(*shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, dt)

    def ones(m):
        return jnp.ones((m,), jnp.float32)

    layers = [dict(
        norm1=ones(h), q=mat(h, cfg.q_width), k=mat(h, cfg.kv_width),
        v=mat(h, cfg.kv_width), o=mat(cfg.q_width, h, std=into_residual),
        q_norm=ones(cfg.head_dim), k_norm=ones(cfg.head_dim),
        iq=mat(h, nj * di), ik=mat(h, di), iw=mat(h, nj),
        ik_norm=ones(di), ik_bias=jnp.zeros((di,), jnp.float32),
        norm2=ones(h), router=mat(h, cfg.num_experts),
        w_gate=mat(n, h, f), w_up=mat(n, h, f),
        w_down=mat(n, f, h, std=into_residual))
        for _ in range(cfg.num_hidden_layers)]
    return {"embed": mat(cfg.vocab_size, h, std=1.0), "layers": layers,
            "norm_f": ones(h), "head": mat(h, cfg.vocab_size)}


# -- pieces -----------------------------------------------------------------
def layer_norm(x, weight, bias, eps):
    x32 = x.astype(jnp.float32)
    c = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    y = c * lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps)
    return (y * weight + bias).astype(x.dtype)


def text_positions(t):
    """The three position streams of `t` text tokens: the token's index in
    each."""
    return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (3, t))


def rope_tables(cfg, positions):
    """(cos, sin) of the attention heads' D/2 angles and of the indexer's
    Di/2, each (T, .) float32, at `positions` (3, T): frequency i of the
    attention turns by its stream's position (`mrope_section`), the indexer
    by stream 0's."""
    half, half_i = cfg.head_dim // 2, cfg.indexer_head_dim // 2
    p = positions.astype(jnp.float32)
    stream = np.repeat(np.arange(3), cfg.mrope_section)            # (D/2,)
    ang = p[stream].T * cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang_i = p[0][:, None] * cfg.rope_theta ** (
        -jnp.arange(half_i, dtype=jnp.float32) / half_i)
    return (jnp.cos(ang), jnp.sin(ang)), (jnp.cos(ang_i), jnp.sin(ang_i))


def attention_inputs(cfg, layer, u, tables):
    """What a layer's attention reads of tokens `u` (T, H) at the positions
    behind `tables` (`rope_tables`): q (T, Hq·D), the cache rows k, v (T,
    Hkv·D) and kI (T, Di), the indexer's qI (T, J, Di) and w (T, J)
    float32; q, k, qI, kI normed and rotated."""
    dt = u.dtype
    t = u.shape[0]
    d, di = cfg.head_dim, cfg.indexer_head_dim
    eps = cfg.rms_norm_eps
    with jax.named_scope("qkv"):
        q = rms_norm((u @ layer["q"].astype(dt)).reshape(t, -1, d),
                     layer["q_norm"], eps)
        k = rms_norm((u @ layer["k"].astype(dt)).reshape(t, -1, d),
                     layer["k_norm"], eps)
        v = u @ layer["v"].astype(dt)
        qi = (u @ layer["iq"].astype(dt)).reshape(t, -1, di)
        ki = layer_norm(u @ layer["ik"].astype(dt), layer["ik_norm"],
                        layer["ik_bias"], eps)
        w = jnp.dot(u, layer["iw"].astype(dt),
                    preferred_element_type=jnp.float32)
    with jax.named_scope("rope"):
        rope, rope_i = tables
        q = rotate(q, rope).reshape(t, -1)
        k = rotate(k, rope).reshape(t, -1)
        qi = rotate(qi, rope_i)
        ki = rotate(ki[:, None, :], rope_i)[:, 0]
    return q, k, v, qi, ki, w


def select_rows(scores, k):
    """`top_k_mask` over `scores` (R, C) in runs of `SELECT_ROWS` rows, so
    that one run's image stays small: (R, C) int8."""
    r, c = scores.shape
    if r <= SELECT_ROWS or r % SELECT_ROWS:
        return top_k_mask(scores, k).astype(jnp.int8)
    runs = r // SELECT_ROWS
    return lax.map(
        lambda a: top_k_mask(*a).astype(jnp.int8),
        (scores.reshape(runs, SELECT_ROWS, c),
         k.reshape(runs, SELECT_ROWS))).reshape(r, c)


def selected_attention(cfg, q, k, v, qi, ki, w, impl="auto", q_block=Q_BLOCK):
    """A whole sequence's attention under the indexer's selection: for
    query blocks of `q_block` rows, the index scores against the keys up to
    the block's end, each row's `min(topk, t + 1)` best as a mask, and
    attention restricted to it. Exact: the dense tiles of scores and of
    attention are all computed, the mask drops what was not selected.
    `attention_inputs`' arrays of one sequence in, (T, Hq·D) out."""
    t = q.shape[0]
    qi = qi.transpose(1, 0, 2)                                # (J, T, Di)
    out = []
    for start in range(0, t, q_block):
        stop = min(t, start + q_block)
        with jax.named_scope("indexer"):
            with jax.named_scope("score"):
                scores = index_scores(
                    qi[:, start:stop], ki[:stop], w[start:stop],
                    cfg.index_scale, q_offset=start, impl=impl)
            with jax.named_scope("select"):
                keep = jnp.minimum(cfg.indexer_topk,
                                   jnp.arange(start, stop) + 1)
                selected = select_rows(scores, keep.astype(jnp.int32))
        out.append(flash_attention_selected(
            q[start:stop], k[:stop], v[:stop], selected,
            cfg.num_key_value_heads, q_offset=start, impl=impl))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)


def router_probs(layer, g):
    """The router's softmax over ALL experts for tokens `g` (T, H),
    float32."""
    return jax.nn.softmax(jnp.dot(g, layer["router"].astype(g.dtype),
                                  preferred_element_type=jnp.float32), -1)


def moe(cfg, layer, g):
    """The expert layer for tokens `g` (T, H): this chip's part of the
    routed SwiGLU experts (those of `cfg.experts_held`), the top-k weights
    normalised over all the chosen. Returns (out (T, H) float32, the
    `routed_experts` counts). Prefill and decode run the same function; a
    long sequence goes through in runs of `MOE_CHUNK` tokens."""
    def run(tokens):
        with jax.named_scope("router"):
            probs = router_probs(layer, tokens)
        with jax.named_scope("experts"):
            return routed_experts(
                tokens, probs, None, layer["w_up"], layer["w_down"],
                cfg.experts_held, cfg.num_experts_per_tok, 1.0,
                jax.nn.silu, w_gate=layer["w_gate"])

    t = g.shape[0]
    if t <= MOE_CHUNK or t % MOE_CHUNK:
        return run(g)
    out, counts = lax.map(run, g.reshape(t // MOE_CHUNK, MOE_CHUNK, -1))
    return out.reshape(t, -1), jnp.concatenate(
        [counts[:, :2].sum(0), counts[:, 2:].max(0)])


def expert_runs(cfg, layer, t):
    """How many times `moe` over t tokens reads the held experts' weights:
    its runs of `MOE_CHUNK`, each as many times as `routed_experts` reads
    them."""
    runs = t // MOE_CHUNK if t > MOE_CHUNK and not t % MOE_CHUNK else 1
    return runs * routed_calls(
        t // runs, cfg.num_experts_per_tok, layer["router"].shape[1],
        cfg.compute_dtype, layer["w_up"], layer["w_down"], layer["w_gate"])


# -- the whole model --------------------------------------------------------
def attention_block(cfg, layer, x, tables, impl="auto", q_block=Q_BLOCK):
    """A layer's first half over a whole sequence `x` (T, H): `h = x +
    attn(RMSNorm(x))`, and the layer's decode state (k, v, kI) as the
    sequence left it."""
    u = rms_norm(x, layer["norm1"], cfg.rms_norm_eps)
    with jax.named_scope("attn"):
        q, k, v, qi, ki, w = attention_inputs(cfg, layer, u, tables)
        ctx = selected_attention(cfg, q, k, v, qi, ki, w, impl, q_block)
        with jax.named_scope("proj"):
            x = x + ctx.astype(x.dtype) @ layer["o"].astype(x.dtype)
    return x, (k, v, ki)


def apply_layer(cfg, layer, x, tables, impl="auto", q_block=Q_BLOCK):
    """One layer over a whole sequence `x` (T, H): the attention block,
    then `y = h + moe(RMSNorm(h))`; returns (y, the decode state)."""
    x, state = attention_block(cfg, layer, x, tables, impl, q_block)
    with jax.named_scope("moe"):
        out, _ = moe(cfg, layer, rms_norm(x, layer["norm2"],
                                          cfg.rms_norm_eps))
    return x + out.astype(x.dtype), state


def encode(cfg, params, ids, positions=None, impl="auto", q_block=Q_BLOCK):
    """The full-sequence forward over ONE sequence `ids` (T,), up to the
    last layer's output (T, H), and every layer's decode state
    (`apply_layer`). `positions` (3, T), by default the text's."""
    if positions is None:
        positions = text_positions(ids.shape[0])
    with jax.named_scope("rope"):
        tables = rope_tables(cfg, positions)
    x = embed(cfg, params, ids)
    states = []
    for li, layer in enumerate(params["layers"]):
        with jax.named_scope(f"layer{li}"):
            x, state = apply_layer(cfg, layer, x, tables, impl, q_block)
        states.append(state)
    return x, states


def forward(cfg, params, ids, positions=None, impl="auto", q_block=Q_BLOCK):
    """Next-token logits at every position of `ids` (T,) or (B, T), the
    rows of a batch one after the other; `positions` (3, T) for all of
    them, by default three copies of `arange(T)`."""
    if ids.ndim == 1:
        return logits(cfg, params, encode(cfg, params, ids, positions, impl,
                                          q_block)[0])
    return jnp.stack([forward(cfg, params, row, positions, impl, q_block)
                      for row in ids])


# -- decode (`generation/decode.py`'s `KeyeDecoder`) ---------------------------
def decode_leaves(cfg):
    """THREE leaves a layer: K and V rows `(C, Hkv·D)` at an even rung of
    C, and the indexer's key rows PACKED two positions a row, `(C / 2,
    2·Di)` (`indexer.pack_rows`: Di = 64 is half a lane tile, and the chip
    gave a `(S, C, 64)` leaf a layout of its own and copied it whole, in
    and out, every step)."""
    dt = cfg.compute_dtype
    layer = (Leaf("k", (cfg.kv_width,), dt), Leaf("v", (cfg.kv_width,), dt),
             Leaf("ki", (2 * cfg.indexer_head_dim,), dt, per_row=2))
    return [layer] * cfg.num_hidden_layers


def attends_in_place(rung, topk):
    """Whether a sparse decode step over a rung of `rung` rows reads K and
    V in place under the selection mask (cost: the rows in use, which the
    rung bounds) or gathers the `topk` kept rows first (cost: 2 x `topk`
    row copies a slot, whatever the context). Both are one softmax over
    the same rows; the rung and `topk` are static shapes of the program
    being compiled, so the program holds one of them."""
    return rung <= IN_PLACE_RUNGS * topk


def decode_context(cfg, pos, leaves, counts):
    """The rows in use at `pos` (S,), 0..pos (the current write included;
    a lane frozen at the end of its rung, pos == C, keeps all of them), the
    `min(topk, pos + 1)` a slot keeps, whether the step reads them in place
    (`attends_in_place`), the rows of K the kernel fetches for them (in
    place: the rows in use rounded up to its tile) and the rotary tables
    (text: all three streams the token's index); the tally from zero."""
    s = pos.shape[0]
    slot = slot_index(pos)
    c = leaves["k"][0].shape[1]
    width = min(cfg.indexer_topk, c)
    in_use = jnp.minimum(pos + 1, c)                        # (S,)
    keep = jnp.minimum(in_use, width)
    in_place = attends_in_place(c, cfg.indexer_topk)
    kept = None
    if in_place:
        tile = decode_tile_rows(c, cfg.kv_width, leaves["k"][0].dtype)
        read = (-(-in_use // tile) * tile).sum()
    else:
        kept = jnp.arange(width)[None, :] < keep[:, None]   # (S, topk)
        read = keep.sum()
    with jax.named_scope("rope"):
        tables = rope_tables(cfg, jnp.broadcast_to(pos[None], (3, s)))
    return dict(pos=pos, slot=slot, width=width, in_use=in_use, keep=keep,
                in_place=in_place, kept=kept, read=read, tables=tables,
                counts=counts), jnp.zeros((3,), jnp.int32)


def decode_layer(cfg, li, layer, x, ctx, state, tally, impl="auto"):
    """Layer `li` for ONE token a slot, `x` (S, H), against its leaves
    `state` = (k, v, ki): the token's K, V and index-key rows written at
    its position, every row in use scored against the slot's index query,
    the kept best attended, read in place under the selection mask or
    gathered into a `(S, topk, Hkv·D)` rung; then its expert layer."""
    s = x.shape[0]
    pos, in_use, in_place = ctx["pos"], ctx["in_use"], ctx["in_place"]
    kc, vc, kic = state
    with jax.named_scope(f"layer{li}"):
        u = rms_norm(x, layer["norm1"], cfg.rms_norm_eps)
        with jax.named_scope("attn"):
            q, k, v, qi, ki, w = attention_inputs(cfg, layer, u,
                                                  ctx["tables"])
            with jax.named_scope("kv_write"):
                kc, vc = write_rows((kc, vc), ctx["slot"], pos, (k, v))
                kic = write_packed_row(kic, pos, ki)
            with jax.named_scope("indexer"):
                with jax.named_scope("score"):
                    scores = index_scores_decode(qi, kic, w, pos,
                                                 cfg.index_scale, impl=impl)
                with jax.named_scope("select"):
                    mask = top_k_mask(scores, ctx["keep"])
                    if not in_place:
                        rows = compact_indices(mask, ctx["width"])
            q = q.reshape(s, cfg.num_attention_heads, cfg.head_dim)
            if in_place:
                out = flash_attention_decode(q, kc, vc, mask, impl=impl,
                                             lengths=in_use)
            else:
                with jax.named_scope("gather"):
                    kg, vg = (jnp.take_along_axis(leaf, rows[..., None],
                                                  axis=1)
                              for leaf in (kc, vc))
                out = flash_attention_decode(q, kg, vg, ctx["kept"],
                                             impl=impl)
            with jax.named_scope("proj"):
                x = x + out.reshape(s, -1).astype(x.dtype) \
                    @ layer["o"].astype(x.dtype)
        with jax.named_scope("moe"):
            out, counts = moe(cfg, layer, rms_norm(x, layer["norm2"],
                                                   cfg.rms_norm_eps))
            tally = tally + counts
        x = x + out.astype(x.dtype)
    return x, (kc, vc, kic), tally


def decode_counts(cfg, ctx, tally):
    """The expert layers' tally, then the rows scored, the rows kept and
    the rows of K the kernel fetched for them, slots and layers summed."""
    return ctx["counts"] + jnp.concatenate([
        tally, cfg.num_hidden_layers * jnp.stack(
            [ctx["in_use"].sum(), ctx["keep"].sum(), ctx["read"]]
        ).astype(jnp.int32)])


def encode_prompt(cfg, params, prompt, plen, impl="auto"):
    """`encode`: its rows past plen are padding that a decode step never
    scores (it masks rows past `pos`)."""
    return encode(cfg, params, prompt, impl=impl)


def cache_parts(cfg, li, state, plen, leaves):
    k, v, ki = state
    return k, v, pack_rows(ki)
