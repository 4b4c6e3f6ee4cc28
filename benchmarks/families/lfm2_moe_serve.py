"""An LFM2-MoE language model (gated short-convolution layers beside
grouped-query attention layers, two leading dense layers, sigmoid-routed
SwiGLU experts with no shared one, a head tied to the embedding) behind
`Lfm2MoeDecoder` and `GenerationServer`: what the serving drivers need,
built from a configuration file's sizes and `--seed`, with the
configuration's copy of the plain reference beside it.

The file holds the source's `config.json` keys at its top level, with the
ones the cut changes (`reduced`) at the values held here and the published
ones under `published`; `held` says which layers, experts and vocabulary
rows this chip has."""
from __future__ import annotations

import os


def model_config(config, dtype):
    """The program's configuration from the file, the experts held the
    file's `held` range (all of the router's here)."""
    from deeplearning4j_tpu.models.lfm2_moe import Lfm2MoeConfig
    first, last = (int(v) for v in config["held"]["experts"])
    return Lfm2MoeConfig.from_dict(config, experts_held=(first, last - first),
                                   dtype=dtype)


class Built:
    def __init__(self, config, seed):
        import jax

        from deeplearning4j_tpu.models.lfm2_moe import init_params

        s = config["serving"]
        self.config = config
        self.seed = int(seed)
        self.cfg = model_config(config, s["dtype"])
        self.slots = int(s["slots"])
        self.vocab = int(config["vocab_size"])
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
        from deeplearning4j_tpu.generation.decode import Lfm2MoeDecoder
        from deeplearning4j_tpu.generation.server import GenerationServer

        s = self.config["serving"]
        os.makedirs(exec_cache_dir, exist_ok=True)
        return GenerationServer(
            Lfm2MoeDecoder(self.cfg, self.params), slots=self.slots,
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
        # same shapes (the controls' eight) traces nothing
        fn = self._reference.setdefault((lower, fault), jax.jit(
            lambda p, x, a: reference_logits(p, x, sizes, at=a,
                                             lower=lower, fault=fault)))
        with jax.default_matmul_precision("highest"):
            out = fn(self.params, rows, where)
        out = np.asarray(out, np.float32)
        return out if ids.ndim == 2 else out[0]


def reference_sizes(config):
    """What the reference needs of a configuration file, as plain numbers
    (it shares no code with `models/lfm2_moe.py`)."""
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size",
            "num_experts_per_tok", "num_dense_layers",
            "routed_scaling_factor", "norm_eps", "conv_L_cache")
    sizes = {k: config[k] for k in keys}
    sizes["rope_theta"] = config["rope_parameters"]["rope_theta"]
    sizes["first_expert"] = int(config["held"]["experts"][0])
    sizes["attention"] = [
        kind == "full_attention" for kind in
        config["layer_types"][:int(config["num_hidden_layers"])]]
    return sizes


#: query rows the reference's attention (and a dense layer) takes at once:
#: attention's scores are heads x rows x T float32, 268 MB at 32 heads and
#: T 16391
REFERENCE_ROWS = 128
#: (token, expert) pairs the reference's expert loop computes at once
REFERENCE_PAIRS = 256
#: added to the chosen scores' sum before the weights are divided by it:
#: transformers' `Lfm2MoeSparseMoeBlock.route_tokens_to_experts`
ROUTE_NORM_EPS = 1e-6


#: faults planted in the reference for the controls that say what the
#: driver's `check()` can see of the new layer and routing (PERF.md,
#: section 6): what a wrong program would compute, not a precision
FAULTS = (
    "conv_one_late",        # the taps read z at t-3..t-1: a tail grafted
                            # or shifted one row off
    "gates_swapped",        # B * conv3(C * xx): the in_proj split in
                            # another order
    "bias_in_weights",      # the expert bias added to the routing weights
                            # as well as to the choice
)


def reference_logits(params, ids, sizes, at=None, lower=False, fault=None):
    """The plain reference: the model's forward as the source's
    `config.json` (`model_type` lfm2_moe) and the family's public modeling
    code (transformers' `Lfm2MoeForCausalLM`: `Lfm2MoeShortConv`,
    `Lfm2MoeAttention`, `Lfm2MoeSparseMoeBlock`) define it, float32
    `jax.numpy`, no kernel, no cache: the convolution an explicit sum of
    its taps over a zero-padded sequence; every query head against its KV
    head's keys under a causal mask made by `where`, one softmax; rotary in
    halves (lane j with lane j + D/2); the router's choice by a SORT and
    the experts a loop, each over the (token, expert) pairs a stable sort
    groups to it. (batch, time) ids -> (batch, time, vocab) logits, or
    (batch, n, vocab) at the positions `at` (batch, n).

    Every layer is `h = x + mixer(N_op(x))`, `y = h + ffn(N_ffn(h))`
    (RMSNorms); the embedding is unscaled and the head is the embedding's
    transpose. Attention and a dense layer run over `REFERENCE_ROWS` rows
    at a time, an expert over `REFERENCE_PAIRS` of its pairs at a time,
    the rows of a batch one after the other: splits of the work, not of the
    mathematics. The routed experts are those HELD here (what the experts
    held elsewhere would add is left out, as in the program; the weights
    are normalised over all the chosen). The tree is the served one; a
    matrix (an expert's) is upcast as it is used.

    `lower` computes one precision below what the configuration states,
    for the reading that sets the driver's tolerance (`PERF.md`): float8
    (e4m3, scaled to the tensor's largest value) wherever the
    configuration has bfloat16 — the weights, each block's normed input,
    `B * x` (what a conv tail holds), the gated convolution, the normed and
    rotated queries, the key and value rows a cache would hold, the
    attention's and a feed-forward's inner result, and the residual stream
    after each block. `fault` plants one of `FAULTS`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    assert fault is None or fault in FAULTS, fault

    f32 = jnp.float32
    eps = sizes["norm_eps"]
    hidden = int(sizes["hidden_size"])
    nh, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = hidden // nh
    group = nh // hkv
    top_e, first = sizes["num_experts_per_tok"], sizes["first_expert"]
    dense_layers = sizes["num_dense_layers"]
    route_scale = float(sizes["routed_scaling_factor"])
    theta = float(sizes["rope_theta"])
    taps = int(sizes["conv_L_cache"])
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

    # lane j turns with lane j + D/2 by t theta^(-2j / D)
    angle = jnp.arange(t, dtype=f32)[:, None] \
        * theta ** (-2.0 * jnp.arange(d // 2, dtype=f32) / d)

    def turn(x):
        """Rotary in halves of heads x (T, heads, D)."""
        a, b = x[..., :d // 2], x[..., d // 2:]
        cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def by_blocks(fn, *per_row):
        """fn over `rows` rows at a time: per_row arrays (T, ...) -> (T,
        ...)."""
        pad = blocks * rows - t
        cut = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (blocks, rows) + a.shape[1:]) for a in per_row]
        out = lax.map(lambda a: fn(*a), tuple(cut))
        return out.reshape((blocks * rows,) + out.shape[2:])[:t]

    def conv(p, u):
        """The gated short convolution: B, C, x in that order from W_in;
        `C * sum_j w[:, j] (B x)[t - K + 1 + j]`, B x zero before position
        0; no bias, no activation."""
        bcx = u @ up(p["in_proj"])
        b, c, x = (bcx[:, i * hidden:(i + 1) * hidden] for i in range(3))
        if fault == "gates_swapped":
            b, c = c, b
        z = act(b * x)
        w = up(p["conv_w"])                                   # (H, K)
        late = 1 if fault == "conv_one_late" else 0
        zp = jnp.concatenate([jnp.zeros((taps - 1 + late, hidden), f32), z])
        s = sum(w[:, j] * zp[j:j + t] for j in range(taps))
        return act(c * s) @ up(p["out_proj"])

    def attention(p, u):
        q = rms((u @ up(p["q"])).reshape(t, nh, d), p["q_norm"])
        k = rms((u @ up(p["k"])).reshape(t, hkv, d), p["k_norm"])
        v = act((u @ up(p["v"])).reshape(t, hkv, d))   # what a cache holds
        q, k = act(turn(q)), act(turn(k))

        def block(q_b, at_b):
            s = jnp.arange(t)[None, :]
            seen = s <= at_b[:, None]                          # (R, T)
            # query head h reads KV head h // group
            a = jnp.einsum("qhd,khd->hqk", q_b, jnp.repeat(k, group, 1)) \
                / jnp.sqrt(float(d))
            prob = jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", prob,
                              jnp.repeat(v, group, 1)).reshape(-1, nh * d)

        o = by_blocks(block, q, jnp.arange(t))
        return act(o) @ up(p["o"])

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
        if fault == "bias_in_weights":
            val = val + p["router_bias"][idx]
        wts = route_scale * val / (val.sum(-1, keepdims=True)
                                   + ROUTE_NORM_EPS)
        # every (token, choice) pair, grouped by expert by a stable sort
        by = jnp.argsort(idx.reshape(-1), stable=True)
        expert = idx.reshape(-1)[by]
        token = (jnp.arange(t * top_e) // top_e)[by]
        weight = wts.reshape(-1)[by]
        pairs = t * top_e

        def one_expert(out, inp):
            e, wg, wu, wd = inp
            wg, wu, wd = up(wg), up(wu), up(wd)
            start = jnp.searchsorted(expert, e, side="left")
            stop = jnp.searchsorted(expert, e, side="right")

            def chunk(state):
                out, at_ = state
                i = at_ + jnp.arange(REFERENCE_PAIRS)
                mine = i < stop
                j = jnp.minimum(i, pairs - 1)
                tok = token[j]
                y = act(jax.nn.silu(g[tok] @ wg) * (g[tok] @ wu)) @ wd
                return out.at[tok].add(
                    jnp.where(mine, weight[j], 0.0)[:, None] * y), \
                    at_ + REFERENCE_PAIRS

            out, _ = lax.while_loop(lambda s: s[1] < stop, chunk,
                                    (out, start))
            return out, None

        out, _ = lax.scan(one_expert, jnp.zeros_like(g), (
            first + jnp.arange(p["w_gate"].shape[0]), p["w_gate"],
            p["w_up"], p["w_down"]))
        return out

    def sequence(row, where):
        x = up(params["embed"])[row]
        for li, p in enumerate(params["layers"]):
            u = rms(x, p["norm_op"], True)
            mixed = attention(p, u) if sizes["attention"][li] else conv(p, u)
            x = act(x + mixed)
            g = rms(x, p["norm_ffn"], True)
            if li < dense_layers:
                f = by_blocks(lambda r: swiglu(r, p["d_gate"], p["d_up"],
                                               p["d_down"]), g)
            else:
                f = moe(p, g)
            x = act(x + f)
        if where is not None:
            x = x[where]
        return rms(x, params["norm_f"], True) @ up(params["embed"]).T

    if at is None:
        return lax.map(lambda row: sequence(row, None), ids)
    return lax.map(lambda a: sequence(*a), (ids, at))


def build(config, seed):
    return Built(config, seed)
