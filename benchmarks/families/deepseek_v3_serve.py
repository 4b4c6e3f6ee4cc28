"""A DeepSeek-V3 style language model (multi-head latent attention, a
leading dense layer, sigmoid-routed SwiGLU experts with shared experts)
behind `MLADecoder` and `GenerationServer`: what the serving drivers need,
built from a configuration file's sizes and `--seed`, with the
configuration's copy of the plain reference beside it.

The file holds the source's `config.json` keys at its top level, with the
ones the cut changes (`reduced`) at the values held here and the published
ones under `published`; `held` says which layers, experts and vocabulary
rows this chip has."""
from __future__ import annotations

import os


def model_config(config, dtype):
    """The program's configuration from the file: the router keeps its
    published width, the experts held are the file's."""
    from deeplearning4j_tpu.models.deepseek_v3 import DeepseekV3Config
    first = int(config["held"]["experts"][0])
    return DeepseekV3Config.from_dict(
        config,
        n_routed_experts=int(config["published"]["n_routed_experts"]),
        experts_held=(first, int(config["n_routed_experts"])), dtype=dtype)


class Built:
    def __init__(self, config, seed):
        import jax

        from deeplearning4j_tpu.models.deepseek_v3 import init_params

        s = config["serving"]
        self.config = config
        self.seed = int(seed)
        self.cfg = model_config(config, s["dtype"])
        self.slots = int(s["slots"])
        self.vocab = int(config["vocab_size"])      # the slice held here
        # the chip's own bit generator, as the other families use it
        key = jax.random.fold_in(
            jax.random.key(self.seed & 0x7FFFFFFF, impl="rbg"),
            self.seed >> 31)
        # every weight on the device in one jitted call
        self.params = jax.block_until_ready(
            jax.jit(lambda k: init_params(self.cfg, k))(key))
        self._reference = {}        # (lower, fault) -> the jitted reference

    def make_server(self, exec_cache_dir, max_new_tokens):
        """The server with the configuration's slots, rungs and buckets and
        NOTHING else named: every scheduler option stays at the program's
        default, so that a PR which changes a default is measured."""
        from deeplearning4j_tpu.generation.decode import MLADecoder
        from deeplearning4j_tpu.generation.server import GenerationServer

        s = self.config["serving"]
        os.makedirs(exec_cache_dir, exist_ok=True)
        return GenerationServer(
            MLADecoder(self.cfg, self.params), slots=self.slots,
            cache_lengths=list(s["cache_lengths"]),
            prompt_buckets=list(s["prompt_buckets"]),
            max_new_tokens=max_new_tokens, seed=self.seed & 0x7FFFFFFF,
            exec_cache_dir=exec_cache_dir)

    def reference_logits(self, ids, at=None, lower=False, fault=None):
        """Next-token logits by the plain reference over the served
        weights: (..., T, vocab) at every position of `ids` ((T,) or
        (batch, T); the model is causal, so rows padded on the right are
        right up to their length), or (..., n, vocab) at the positions
        `at` ((n,) or (batch, n)) alone. `lower` and `fault` (one of
        `FAULTS`) are for the controls of the driver's tolerance."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        sizes = reference_sizes(self.config)
        ids = np.asarray(ids, np.int32)
        rows = jnp.atleast_2d(jnp.asarray(ids))
        where = None if at is None else jnp.atleast_2d(
            jnp.asarray(np.asarray(at, np.int32)))
        # one jitted function a precision, so that a second call at the
        # same shapes (the control's eight) traces nothing
        fn = self._reference.setdefault((lower, fault), jax.jit(
            lambda p, x, a: reference_logits(p, x, sizes, at=a,
                                             lower=lower, fault=fault)))
        with jax.default_matmul_precision("highest"):
            out = fn(self.params, rows, where)
        out = np.asarray(out, np.float32)
        return out if ids.ndim == 2 else out[0]


def reference_sizes(config):
    """What the reference needs of a configuration file, as plain numbers
    (it shares no code with `models/deepseek_v3.py`)."""
    keys = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
            "first_k_dense_replace", "routed_scaling_factor",
            "rms_norm_eps", "rope_theta")
    sizes = {k: config[k] for k in keys}
    sizes["first_expert"] = int(config["held"]["experts"][0])
    return sizes


#: query rows the reference's attention takes at once (their scores are
#: heads x rows x T float32: 268 MB at 32 heads and T 16392)
REFERENCE_ROWS = 128


#: faults planted in the reference's ATTENTION for the controls that say
#: what the driver's `check()` can see of the latent cache (PERF.md,
#: Findings, PR 37): what a wrong program would compute, not a precision
FAULTS = (
    "attention_zeroed",     # the attention's output left out of the stream
    "latent_shifted",       # row s holds position s - 1's latent beside
                            # position s's rotary key: a latent written
                            # into the wrong half of a packed row
    "odd_rows_dropped",     # the rows of odd positions masked: the second
                            # half of every packed row never attended
)


def reference_logits(params, ids, sizes, at=None, lower=False, fault=None):
    """The plain reference: the model's forward as ISSUE 37 writes its
    equations (the source's `config.json`, `model_type` deepseek_v3),
    float32 `jax.numpy`, no kernel, no cache, no grouped product, attention
    in the EXPANDED form (every head's keys and values decompressed from
    the latent, a plain causal mask, one softmax), rotary in interleaved
    pairs, the experts a loop, the router's choice by a SORT. (batch, time)
    ids -> (batch, time, vocab) logits, or (batch, n, vocab) at the
    positions `at` (batch, n).

    Every layer is `h = x + attn(RMSNorm(x))`, `y = h + ffn(RMSNorm(h))`;
    the layers below `first_k_dense_replace` have a dense SwiGLU, the rest
    the experts. Attention runs over `REFERENCE_ROWS` query rows at a time
    against ALL keys, the rows of a batch one after the other: a split of
    the work, not of the mathematics. The routed experts are a loop over
    the experts HELD here, each over every token under a mask (what the
    experts held elsewhere would add is left out, as in the program; the
    weights are normalised over all the chosen); the shared experts are one
    SwiGLU on every token. The tree is the served one; a layer (an expert)
    is upcast as it is used.

    `lower` computes one precision below what the configuration states,
    for the reading that sets the driver's tolerance (`PERF.md`): float8
    (e4m3, scaled to the tensor's largest value) wherever the
    configuration has bfloat16 — the weights, each block's normed input,
    the rotated queries, the latent and rotary-key rows a cache would hold,
    the attention's and a feed-forward's inner result, and the residual
    stream after each block. `fault` plants one of `FAULTS` in every
    layer's attention."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    assert fault is None or fault in FAULTS, fault

    f32 = jnp.float32
    eps = sizes["rms_norm_eps"]
    nh, lat = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, vd = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    top_e, first = sizes["num_experts_per_tok"], sizes["first_expert"]
    dense_layers = sizes["first_k_dense_replace"]
    route_scale = float(sizes["routed_scaling_factor"])
    theta = float(sizes["rope_theta"])
    t = ids.shape[1]
    rows = min(REFERENCE_ROWS, t)
    blocks = -(-t // rows)

    def float8(v):
        scale = jnp.max(jnp.abs(v)) / 448.0 + 1e-30
        return (v / scale).astype(jnp.float8_e4m3fn).astype(f32) * scale

    def up(w):
        return float8(w.astype(f32)) if lower else w.astype(f32)

    def act(v):
        """An activation the served path holds in bfloat16."""
        return float8(v) if lower else v

    def rms(x, w, block_input=False):
        u = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w
        return act(u) if block_input else u

    # lanes (2i, 2i + 1) turn by t theta^(-2i / R)
    angle = jnp.arange(t, dtype=f32)[:, None] \
        * theta ** (-2.0 * jnp.arange(rope // 2, dtype=f32) / rope)

    def turn(x):
        """Rotary in interleaved pairs of heads x (T, heads, R)."""
        pairs = x.reshape(x.shape[:-1] + (rope // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)

    def by_blocks(fn, *per_row):
        """fn over `rows` query rows at a time: per_row arrays (T, ...)
        -> (T, ...)."""
        pad = blocks * rows - t
        cut = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (blocks, rows) + a.shape[1:]) for a in per_row]
        out = lax.map(lambda a: fn(*a), tuple(cut))
        return out.reshape((blocks * rows,) + out.shape[2:])[:t]

    def attention(p, u):
        q = (u @ up(p["q"])).reshape(t, nh, nope + rope)
        kva = u @ up(p["kva"])
        c = act(rms(kva[:, :lat], p["kv_norm"]))      # what a cache holds
        kr = act(turn(kva[:, None, lat:]))            # (T, 1, R)
        if fault == "latent_shifted":
            c = jnp.pad(c, ((1, 0), (0, 0)))[:t]
        q = act(jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1))
        k = jnp.concatenate(
            [(c @ up(p["k_up"])).reshape(t, nh, nope),
             jnp.broadcast_to(kr, (t, nh, rope))], -1)
        v = (c @ up(p["v_up"])).reshape(t, nh, vd)

        def block(q_b, at_b):
            seen = jnp.arange(t)[None, :] <= at_b[:, None]    # (R, T)
            if fault == "odd_rows_dropped":
                seen = seen & (jnp.arange(t)[None, :] % 2 == 0)
            a = jnp.einsum("qhd,khd->hqk", q_b, k) \
                / jnp.sqrt(float(nope + rope))
            prob = jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", prob, v).reshape(-1, nh * vd)

        out = act(by_blocks(block, q, jnp.arange(t))) @ up(p["o"])
        return jnp.zeros_like(out) if fault == "attention_zeroed" else out

    def swiglu(g, wg, wu, wd):
        return act(jax.nn.silu(g @ up(wg)) * (g @ up(wu))) @ up(wd)

    def moe(p, g):
        score = jax.nn.sigmoid(g @ up(p["router"]))           # (T, E)
        # the top_e experts of largest score + bias, by a sort; the
        # weights are the scores themselves
        order = jnp.argsort(-(score + p["router_bias"]), axis=-1,
                            stable=True)
        idx = order[:, :top_e]
        val = jnp.take_along_axis(score, idx, axis=-1)
        wts = route_scale * val / (val.sum(-1, keepdims=True) + 1e-20)

        def expert(acc, inp):
            j, wg, wu, wd = inp
            w_tok = jnp.sum(jnp.where(idx == first + j, wts, 0.0), -1)
            return acc + w_tok[:, None] * swiglu(g, wg, wu, wd), None

        out, _ = lax.scan(expert, jnp.zeros_like(g), (
            jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
            p["w_down"]))
        return out + swiglu(g, p["s_gate"], p["s_up"], p["s_down"])

    def sequence(row, where):
        x = up(params["embed"])[row]
        for li, p in enumerate(params["layers"]):
            x = act(x + attention(p, rms(x, p["norm1"], True)))
            g = rms(x, p["norm2"], True)
            x = act(x + (swiglu(g, p["gate"], p["up"], p["down"])
                         if li < dense_layers else moe(p, g)))
        if where is not None:
            x = x[where]
        return rms(x, params["norm_f"], True) @ up(params["head"])

    if at is None:
        return lax.map(lambda row: sequence(row, None), ids)
    return lax.map(lambda a: sequence(*a), (ids, at))


def build(config, seed):
    return Built(config, seed)
