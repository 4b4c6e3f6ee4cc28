"""Nemotron-H style hybrid language model: Mamba-2, attention and LatentMoE
layers in one stack.

The second model family beside `models/bert.py`. A layer pattern (one
letter a layer) picks each layer's mixer, and every layer is pre-norm with
one mixer: `x <- x + mixer_i(RMSNorm(x))`; after the last, `RMSNorm` and an
untied head. No biases but the convolution's. The mixers:

- `M`, Mamba-2 (Dao & Gu, arXiv:2405.21060): `[z | xBC | dt] = u W_in`; a
  causal depthwise convolution and SiLU over `xBC`, split into `x` (heads),
  `B`, `C` (groups); `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`; the
  state `H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t`, `y_t = H_t C_t + D
  x_t`; `y * silu(z)`, RMSNorm over each group's lanes, `W_out`. A full
  sequence runs the CHUNKED form of the scan (`ssd_chunked`: products
  inside a chunk, the state carried between chunks); a decode step
  (`mamba_step`) advances the state by one token. The state and its
  arithmetic are float32 whatever the compute dtype.
- `*`, grouped-query attention with NO position encoding (the Nemotron-H
  report, arXiv:2504.03624): `q` heads read the KV head of their group.
- `E`, LatentMoE: a sigmoid router over ALL experts on the full width (top-k
  of score + correction bias, weights normalised over the chosen and
  scaled), the routed experts `relu(l W1)^2 W2` in a narrower latent `l = u
  W_down` through `parallel.moe.routed_experts` for the experts HELD here,
  `W_up` back, beside one shared expert at full width.

`encode` is the full-sequence forward (prefill's arithmetic) and hands back
each layer's decode state; `generation/decode.py`'s `NemotronHDecoder`
serves it from the decode state declared here (`decode_leaves`) and the
step's and the prefill's hooks. Multi-token prediction modules are not
modelled.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.kernels.flash_attention import (
    flash_attention, flash_attention_decode)
# (RMSNorm and the router's sigmoid are every served decoder's)
from deeplearning4j_tpu.models.decoder_common import (  # noqa: F401
    Leaf, conv_shift, conv_tail, embed, rms_norm, router_scores, slot_index,
    write_rows)
from deeplearning4j_tpu.parallel.moe import routed_calls, routed_experts
from deeplearning4j_tpu.parallel.ring_attention import dense_attention

_HIGHEST = lax.Precision.HIGHEST


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int
    hidden_size: int
    pattern: str                    # one of "M", "*", "E" a layer
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    n_routed_experts: int           # the router's outputs: ALL experts
    experts_held: tuple             # (first, count) of those held here
    num_experts_per_tok: int
    moe_latent_size: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float
    norm_eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    dtype: str = "float32"          # compute and weight dtype

    def __post_init__(self):
        bad = set(self.pattern) - set("M*E")
        if bad or not self.pattern:
            raise ValueError(f"pattern must be made of 'M', '*', 'E': "
                             f"{self.pattern!r}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.mamba_num_heads % self.n_groups:
            raise ValueError("query heads must be a multiple of KV heads, "
                             "Mamba heads of groups")
        first, count = self.experts_held
        if first < 0 or count < 1 \
                or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_routed_experts}")

    @classmethod
    def from_dict(cls, d, **over):
        """From a `config.json`'s keys (`model_type` nemotron_h). The file's
        `n_routed_experts` is the router's width; `experts_held` defaults
        to all of them."""
        e = int(d["n_routed_experts"])
        kw = {k: int(d[k]) for k in (
            "vocab_size", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "mamba_num_heads",
            "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
            "chunk_size", "n_routed_experts", "num_experts_per_tok",
            "moe_latent_size", "moe_intermediate_size",
            "moe_shared_expert_intermediate_size")}
        kw.update({k: float(d[k]) for k in (
            "routed_scaling_factor", "norm_eps", "time_step_min",
            "time_step_max", "time_step_floor")})
        kw.update(pattern=d["hybrid_override_pattern"], experts_held=(0, e))
        kw.update(over)
        kw["experts_held"] = tuple(int(v) for v in kw["experts_held"])
        return cls(**kw)

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def q_width(self):
        return self.num_attention_heads * self.head_dim

    @property
    def kv_width(self):
        return self.num_key_value_heads * self.head_dim


# -- parameters -------------------------------------------------------------
def init_params(cfg, key):
    """Seeded weights: matrices normal 0.02 in the compute dtype, the
    convolution's K taps a lane normal 1/sqrt(K); the small vectors
    float32: `A_log = log U[1, 16]`, `dt_bias` the inverse softplus
    of a log-uniform step in [`time_step_min`, `time_step_max`] floored at
    `time_step_floor`, `D` and the norm weights 1, the router's correction
    bias normal 0.01 (so that the bias path counts).

    The projections that read a one-signed activation (`out_proj` after
    the SiLU-gated scan output, an expert's second matrix after ReLU²) are
    drawn the same way and then CENTRED over their input rows (`centred`).
    Drawn plainly, the activation's mean times the sum of the matrix's rows
    is one vector that every token adds to the residual stream; after a few
    layers it is most of every row (cosine 0.78 between tokens at the last
    layer of an 11-layer stack at the published widths), every token ranks
    the experts alike, and WHICH experts they all choose is the seed's
    accident. Trained weights carry no such vector."""
    dt = cfg.compute_dtype
    h = cfg.hidden_size
    keys = iter(jax.random.split(key, 4 + 10 * len(cfg.pattern)))

    def mat(*shape, scale=0.02):
        return scale * jax.random.normal(next(keys), shape, dt)

    def centred(*shape):
        """`mat` with every output column's mean over the input rows (the
        axis before the last) taken off."""
        w = mat(*shape)
        return w - w.mean(-2, keepdims=True, dtype=jnp.float32).astype(dt)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    layers = []
    for kind in cfg.pattern:
        layer = {"norm": ones(h)}
        if kind == "M":
            nh = cfg.mamba_num_heads
            step = jnp.exp(
                jax.random.uniform(next(keys), (nh,), jnp.float32)
                * (math.log(cfg.time_step_max)
                   - math.log(cfg.time_step_min))
                + math.log(cfg.time_step_min))
            step = jnp.maximum(step, cfg.time_step_floor)
            layer.update(
                in_proj=mat(h, cfg.d_inner + cfg.conv_dim + nh),
                conv_w=mat(cfg.conv_dim, cfg.conv_kernel,
                           scale=cfg.conv_kernel ** -0.5),
                conv_b=jnp.zeros((cfg.conv_dim,), jnp.float32),
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                A_log=jnp.log(jax.random.uniform(
                    next(keys), (nh,), jnp.float32, 1.0, 16.0)),
                D=ones(nh), gate_norm=ones(cfg.d_inner),
                out_proj=centred(cfg.d_inner, h))
        elif kind == "*":
            layer.update(qkv=mat(h, cfg.q_width + 2 * cfg.kv_width),
                         o=mat(cfg.q_width, h))
        else:
            n = cfg.experts_held[1]
            lat, f = cfg.moe_latent_size, cfg.moe_intermediate_size
            fs = cfg.moe_shared_expert_intermediate_size
            layer.update(
                router=mat(h, cfg.n_routed_experts),
                e_bias=0.01 * jax.random.normal(
                    next(keys), (cfg.n_routed_experts,), jnp.float32),
                down=mat(h, lat), w1=mat(n, lat, f), w2=centred(n, f, lat),
                up=mat(lat, h), shared_w1=mat(h, fs),
                shared_w2=centred(fs, h))
        layers.append(layer)
    return {"embed": mat(cfg.vocab_size, h), "layers": layers,
            "norm_f": ones(h), "head": mat(h, cfg.vocab_size)}


# -- pieces -----------------------------------------------------------------
def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _split_in_proj(cfg, zxbcdt):
    di, cd = cfg.d_inner, cfg.conv_dim
    return zxbcdt[..., :di], zxbcdt[..., di:di + cd], zxbcdt[..., di + cd:]


def _split_xbc(cfg, xbc):
    """The convolved lanes as x (..., heads, head_dim) and B, C (...,
    groups, state)."""
    di, gn = cfg.d_inner, cfg.n_groups * cfg.ssm_state_size
    lead = xbc.shape[:-1]
    return (xbc[..., :di].reshape(lead + (cfg.mamba_num_heads,
                                          cfg.mamba_head_dim)),
            xbc[..., di:di + gn].reshape(lead + (cfg.n_groups,
                                                 cfg.ssm_state_size)),
            xbc[..., di + gn:].reshape(lead + (cfg.n_groups,
                                               cfg.ssm_state_size)))


def _conv_silu(window, layer):
    """`window` (..., K, lanes): the K rows ending at each position.
    Depthwise: lane c of the output is silu(b_c + sum_j w[c, j] row_j)."""
    w = layer["conv_w"].astype(jnp.float32)
    acc = layer["conv_b"]
    for j in range(w.shape[1]):
        acc = acc + window[..., j, :].astype(jnp.float32) * w[:, j]
    return jax.nn.silu(acc)


def _gate_and_norm(cfg, layer, y, z):
    """`y * silu(z)`, then RMSNorm over each group's lanes."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    lead = g.shape[:-1]
    g = g.reshape(lead + (cfg.n_groups, cfg.d_inner // cfg.n_groups))
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                      + cfg.norm_eps)
    return g.reshape(lead + (cfg.d_inner,)) * layer["gate_norm"]


def ssd_chunked(x, dt, a, b, c, chunk):
    """The Mamba-2 scan over a whole sequence, chunked (state-space
    duality): inside a chunk of `chunk` positions the outputs are products
    of decay-masked `C B^T` with `dt x`; between chunks one state a head is
    carried. All float32, products at the highest matmul precision.

    - x (B, T, heads, head_dim); dt (B, T, heads), already through its
      softplus, 0 at a position that must leave the state alone
    - a (heads,), negative; b, c (B, T, groups, state)

    Returns (y (B, T, heads, head_dim) without the `D x` term, the state
    after the last position (B, heads, head_dim, state)). From a zero
    state; T need not be a multiple of `chunk`."""
    bsz, t, nh, hd = x.shape
    g, n = b.shape[2], b.shape[3]
    r = nh // g
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                               * (v.ndim - 2)) for v in (x, dt, b, c))
    nc = (t + pad) // chunk
    da = (dt * a).reshape(bsz, nc, chunk, g, r)
    cs = jnp.cumsum(da, axis=2)                        # (B, nc, L, g, r)
    xdt = (x * dt[..., None]).reshape(bsz, nc, chunk, g, r, hd)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    # inside a chunk: position l reads position s <= l through the decay
    # exp(cs_l - cs_s)
    seg = cs[:, :, :, None] - cs[:, :, None, :]        # (B, nc, l, s, g, r)
    low = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(low, seg, -jnp.inf))
    cb = jnp.einsum("bzlgn,bzsgn->bzlsg", c, b, precision=_HIGHEST)
    y = jnp.einsum("bzlsgr,bzsgrp->bzlgrp", cb[..., None] * decay, xdt,
                   precision=_HIGHEST)
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cs[:, :, -1:] - cs)               # (B, nc, L, g, r)
    add = jnp.einsum("bzsgn,bzsgrp->bzgrpn", b, xdt * to_end[..., None],
                     precision=_HIGHEST)
    whole = jnp.exp(cs[:, :, -1])                      # (B, nc, g, r)

    def carry(hstate, inp):
        add_z, whole_z = inp
        return hstate * whole_z[..., None, None] + add_z, hstate

    last, before = lax.scan(
        carry, jnp.zeros((bsz, g, r, hd, n), jnp.float32),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(whole, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                # (B, nc, g, r, p, n)
    y = y + jnp.einsum("bzlgn,bzgrpn->bzlgrp", c, before,
                       precision=_HIGHEST) * jnp.exp(cs)[..., None]
    return (y.reshape(bsz, nc * chunk, nh, hd)[:, :t],
            last.reshape(bsz, nh, hd, n))


def mamba_mixer(cfg, layer, u, plen):
    """The Mamba-2 mixer over whole sequences `u` (B, T, H). Positions at
    and after `plen` (B,) leave the state alone (their dt is 0), so the
    state handed back is the one the last REAL token left, and the
    convolution's tail is the K-1 rows before `plen`. Returns (out, (state
    (B, heads, head_dim, state_size) float32, tail (B, K-1, conv lanes)))."""
    dt_ = u.dtype
    t = u.shape[1]
    k = cfg.conv_kernel
    with jax.named_scope("in_proj"):
        z, xbc, dt = _split_in_proj(cfg, u @ layer["in_proj"].astype(dt_))
    with jax.named_scope("conv"):
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        window = jnp.stack([padded[:, j:j + t] for j in range(k)], axis=2)
        x, b, c = _split_xbc(cfg, _conv_silu(window, layer))
        tail = conv_tail(padded, plen, k - 1)
    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
        dt = jnp.where(jnp.arange(t)[None, :, None] < plen[:, None, None],
                       dt, 0.0)
        y, state = ssd_chunked(x, dt, -jnp.exp(layer["A_log"]), b, c,
                               cfg.chunk_size)
        y = y + layer["D"][:, None] * x
        y = _gate_and_norm(cfg, layer, y.reshape(y.shape[:2] + (-1,)), z)
    with jax.named_scope("out_proj"):
        out = y.astype(dt_) @ layer["out_proj"].astype(dt_)
    return out, (state, tail)


def mamba_step(cfg, layer, u, state, tail):
    """One decode step of the mixer for a batch of slots: `u` (S, H), the
    slots' `state` (S, heads, head_dim, state_size) float32 and `tail` (S,
    K-1, conv lanes). Returns (out (S, H), state', tail')."""
    dt_ = u.dtype
    r = cfg.mamba_num_heads // cfg.n_groups
    with jax.named_scope("in_proj"):
        z, xbc, dt = _split_in_proj(cfg, u @ layer["in_proj"].astype(dt_))
    with jax.named_scope("conv"):
        (x, b, c), tail = conv_shift(
            tail, xbc, lambda w: _split_xbc(cfg, _conv_silu(w, layer)))
    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
        keep = jnp.exp(dt * -jnp.exp(layer["A_log"]))           # (S, heads)
        b, c = (jnp.repeat(v, r, axis=1) for v in (b, c))       # per head
        state = state * keep[..., None, None] \
            + (dt[..., None] * x)[..., None] * b[:, :, None, :]
        y = (state * c[:, :, None, :]).sum(-1) + layer["D"][:, None] * x
        y = _gate_and_norm(cfg, layer, y.reshape(y.shape[0], -1), z)
    with jax.named_scope("out_proj"):
        out = y.astype(dt_) @ layer["out_proj"].astype(dt_)
    return out, state, tail


def attention_qkv(cfg, layer, u):
    """q (..., Hq·D) and the KV rows k, v (..., Hkv·D) of `u`."""
    qkv = u @ layer["qkv"].astype(u.dtype)
    qw, kw = cfg.q_width, cfg.kv_width
    return qkv[..., :qw], qkv[..., qw:qw + kw], qkv[..., qw + kw:]


def _causal_attention(cfg, q, k, v):
    """Causal grouped-query attention over whole sequences: q (B, T, Hq·D),
    k, v (B, T, Hkv·D) -> (B, T, Hq·D). The flash kernel on the TPU, the
    dense form elsewhere; each KV head is repeated over its group."""
    bsz, t = q.shape[:2]
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim

    def heads(a, n):
        return a.reshape(bsz, t, n, d).transpose(0, 2, 1, 3)

    q = heads(q, hq)
    k, v = (jnp.repeat(heads(a, hkv), hq // hkv, axis=1) for a in (k, v))
    if jax.default_backend() == "tpu":
        ctx = flash_attention(q, k, v, causal=True)
    else:
        ctx = dense_attention(q, k, v, causal=True)
    return ctx.transpose(0, 2, 1, 3).reshape(bsz, t, hq * d)


def moe_mixer(cfg, layer, u):
    """The LatentMoE mixer for tokens `u` (T, H): this chip's part of the
    routed experts (those of `cfg.experts_held`) beside the shared expert.
    Returns (out (T, H), the `routed_experts` counts). Prefill and decode
    run the same function."""
    dt_ = u.dtype
    with jax.named_scope("router"):
        scores = router_scores(layer, u)
    with jax.named_scope("latent_down"):
        lat = u @ layer["down"].astype(dt_)
    with jax.named_scope("experts"):
        routed, counts = routed_experts(
            lat, scores, layer["e_bias"], layer["w1"], layer["w2"],
            cfg.experts_held, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, relu2)
    with jax.named_scope("latent_up"):
        out = routed.astype(dt_) @ layer["up"].astype(dt_)
    with jax.named_scope("shared"):
        out = out + relu2(u @ layer["shared_w1"].astype(dt_)) \
            @ layer["shared_w2"].astype(dt_)
    return out, counts


def expert_runs(cfg, layer, t):
    """How many times `moe_mixer` over t tokens reads the held experts'
    weights: as many times as `routed_experts` reads them."""
    return routed_calls(t, cfg.num_experts_per_tok,
                        layer["router"].shape[1], cfg.compute_dtype,
                        layer["w1"], layer["w2"])


# -- the whole model --------------------------------------------------------
def apply_layer(cfg, kind, layer, x, plen):
    """One layer of the pattern over whole sequences: `x + mixer(RMSNorm(
    x))` for `x` (B, T, H), and the layer's decode state as the sequence
    left it: (k, v) rows (B, T, Hkv·D) of an attention layer, (state, tail)
    of a Mamba-2 layer (see `mamba_mixer`), None of an expert layer."""
    bsz, t = x.shape[:2]
    with jax.named_scope("norm"):
        u = rms_norm(x, layer["norm"], cfg.norm_eps)
    if kind == "M":
        with jax.named_scope("ssm"):
            out, state = mamba_mixer(cfg, layer, u, plen)
    elif kind == "*":
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                q, k, v = attention_qkv(cfg, layer, u)
            ctx = _causal_attention(cfg, q, k, v)
            with jax.named_scope("proj"):
                out = ctx.astype(x.dtype) @ layer["o"].astype(x.dtype)
            state = (k, v)
    else:
        with jax.named_scope("moe"):
            out, _ = moe_mixer(cfg, layer, u.reshape(bsz * t, -1))
            out, state = out.reshape(bsz, t, -1), None
    return x + out.astype(x.dtype), state


def encode(cfg, params, ids, plen=None):
    """The full-sequence forward over `ids` (B, T), up to the last layer's
    output (B, T, H), and every layer's decode state (`apply_layer`).
    Positions at and after `plen` (B,), by default T, are padding."""
    if plen is None:
        plen = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
    x = embed(cfg, params, ids)
    states = []
    for li, (kind, layer) in enumerate(zip(cfg.pattern, params["layers"])):
        with jax.named_scope(f"layer{li}"):
            x, state = apply_layer(cfg, kind, layer, x, plen)
        states.append(state)
    return x, states


def logits(cfg, params, x):
    """The final norm and the head over hidden rows `x` (..., H), float32."""
    with jax.named_scope("logits"):
        u = rms_norm(x, params["norm_f"], cfg.norm_eps)
        return jnp.dot(u, params["head"].astype(u.dtype),
                       preferred_element_type=jnp.float32)


def forward(cfg, params, ids):
    """Next-token logits (B, T, V) at every position of `ids` (B, T)."""
    return logits(cfg, params, encode(cfg, params, ids)[0])


# -- decode (`generation/decode.py`'s `NemotronHDecoder`) -----------------------
def decode_leaves(cfg):
    """An ATTENTION layer's K and V leaves, C rows `(C, Hkv·D)` at a rung
    of C; a MAMBA layer's float32 state `(heads, head_dim, state)` and
    convolution tail `(K-1, conv lanes)`, which no rung touches; nothing
    for an expert layer."""
    dt = cfg.compute_dtype
    kv = tuple(Leaf(name, (cfg.kv_width,), dt) for name in ("k", "v"))
    mamba = (Leaf("ssm", (cfg.mamba_num_heads, cfg.mamba_head_dim,
                          cfg.ssm_state_size), jnp.float32, per_row=0,
                  scope="ssm/state_write"),
             Leaf("conv", (cfg.conv_kernel - 1, cfg.conv_dim), dt,
                  per_row=0, scope="ssm/state_write"))
    return [{"*": kv, "M": mamba}.get(kind, ()) for kind in cfg.pattern]


def decode_context(cfg, pos, leaves, counts):
    """The slots' row index and the rows in use at `pos` (S,), 0..pos (the
    current write included); the tally is the running counts."""
    ctx = dict(pos=pos, slot=slot_index(pos), cmask=None)
    if leaves.get("k"):
        ctx["cmask"] = jnp.arange(leaves["k"][0].shape[1])[None, :] \
            <= pos[:, None]
    return ctx, counts


def decode_layer(cfg, li, layer, x, ctx, state, tally, impl="auto"):
    """Layer `li` for ONE token a slot, `x` (S, H): an attention layer
    writes its K/V row at the position in `state` = (k, v) and attends
    rows 0..pos; a Mamba-2 layer advances `state` = (state, tail) by the
    token; an expert layer routes the S tokens and adds what it computed
    to the tally."""
    s = x.shape[0]
    kind = cfg.pattern[li]
    with jax.named_scope(f"layer{li}"):
        with jax.named_scope("norm"):
            u = rms_norm(x, layer["norm"], cfg.norm_eps)
        if kind == "M":
            with jax.named_scope("ssm"):
                out, *state = mamba_step(cfg, layer, u, *state)
        elif kind == "*":
            kc, vc = state
            with jax.named_scope("attn"):
                with jax.named_scope("qkv"):
                    q, k, v = attention_qkv(cfg, layer, u)
                with jax.named_scope("kv_write"):
                    kc, vc = write_rows((kc, vc), ctx["slot"], ctx["pos"],
                                        (k, v))
                o = flash_attention_decode(
                    q.reshape(s, cfg.num_attention_heads, cfg.head_dim),
                    kc, vc, ctx["cmask"], impl=impl)
                with jax.named_scope("proj"):
                    out = o.reshape(s, -1).astype(x.dtype) \
                        @ layer["o"].astype(x.dtype)
            state = (kc, vc)
        else:
            with jax.named_scope("moe"):
                out, counted = moe_mixer(cfg, layer, u)
                tally = tally + counted
        x = x + out.astype(x.dtype)
    return x, tuple(state), tally


def decode_counts(cfg, ctx, tally):
    return tally


def encode_prompt(cfg, params, prompt, plen, impl="auto"):
    """`encode` over the prompt as a batch of one."""
    return encode(cfg, params, prompt[None], jnp.reshape(plen, (1,)))


def cache_parts(cfg, li, state, plen, leaves):
    """An attention layer's K/V rows 0..P-1, a Mamba-2 layer's state and
    tail as the last REAL token left them (`mamba_mixer` holds the state
    through the padding)."""
    return state
