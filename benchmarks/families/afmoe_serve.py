"""An AFMoE language model (sliding-window and full grouped-query attention
layers, a gated attention output, sandwich norms, two leading dense layers,
sigmoid-routed SwiGLU experts with one shared) behind `AfmoeDecoder` and
`GenerationServer`: what the serving drivers need, built from a
configuration file's sizes and `--seed`, with the configuration's copy of
the plain reference beside it.

The file holds the source's `config.json` keys at its top level, with the
ones the cut changes (`reduced`) at the values held here and the published
ones under `published`; `held` says which layers, experts and vocabulary
rows this chip has."""
from __future__ import annotations

import os


def model_config(config, dtype):
    """The program's configuration from the file: the router keeps its
    published width, the experts held are the file's."""
    from deeplearning4j_tpu.models.afmoe import AfmoeConfig
    first = int(config["held"]["experts"][0])
    return AfmoeConfig.from_dict(
        config, num_experts=int(config["published"]["num_experts"]),
        experts_held=(first, int(config["num_experts"])), dtype=dtype)


class Built:
    def __init__(self, config, seed):
        import jax

        from deeplearning4j_tpu.models.afmoe import init_params

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
        from deeplearning4j_tpu.generation.decode import AfmoeDecoder
        from deeplearning4j_tpu.generation.server import GenerationServer

        s = self.config["serving"]
        os.makedirs(exec_cache_dir, exist_ok=True)
        return GenerationServer(
            AfmoeDecoder(self.cfg, self.params), slots=self.slots,
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
    (it shares no code with `models/afmoe.py`)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts_per_tok", "num_dense_layers", "route_scale",
            "rms_norm_eps", "rope_theta", "sliding_window",
            "global_attn_every_n_layers", "hidden_size")
    sizes = {k: config[k] for k in keys}
    sizes["first_expert"] = int(config["held"]["experts"][0])
    sizes["full"] = [kind == "full_attention" for kind in
                     config["layer_types"][:int(config["num_hidden_layers"])]]
    sizes["mup"] = bool(config["mup_enabled"])
    return sizes


#: query rows the reference's attention takes at once (their scores are
#: heads x rows x T float32: 268 MB at 32 heads and T 16392)
REFERENCE_ROWS = 128


#: faults planted in the reference's ATTENTION for the controls that say
#: what the driver's `check()` can see of the two kinds of layer (PERF.md,
#: section 6): what a wrong program would compute, not a precision
FAULTS = (
    "window_ignored",       # the sliding layers attend every earlier
                            # position: a ring that kept too much
    "rope_on_global",       # the full layers rotated too
    "gate_dropped",         # the sigmoid output gate left out
)


def reference_logits(params, ids, sizes, at=None, lower=False, fault=None):
    """The plain reference: the model's forward as the source's
    `config.json` (`model_type` afmoe) and the family's public modeling
    code (transformers' `AfmoeForCausalLM`) define it, float32
    `jax.numpy`, no kernel, no cache, no grouped product: every query head
    against its KV head's keys under a mask made by `where` (a band on a
    sliding layer, the causal triangle on a full one), one softmax; rotary
    in halves (lane j with lane j + D/2) on the sliding layers and on no
    other; the experts a loop, the router's choice by a SORT. (batch, time)
    ids -> (batch, time, vocab) logits, or (batch, n, vocab) at the
    positions `at` (batch, n).

    Every layer is `h = x + N_post_attn(attn(N_in(x)))`, `y = h +
    N_post_mlp(ffn(N_pre_mlp(h)))` (RMSNorms); the embedding is scaled by
    sqrt(hidden) under `mup`. Attention runs over `REFERENCE_ROWS` query
    rows at a time against ALL keys, the rows of a batch one after the
    other: a split of the work, not of the mathematics. The routed experts
    are a loop over the experts HELD here, each over every token under a
    mask (what the experts held elsewhere would add is left out, as in the
    program; the weights are normalised over all the chosen); the shared
    expert is one SwiGLU on every token. The tree is the served one; a
    layer (an expert) is upcast as it is used.

    `lower` computes one precision below what the configuration states,
    for the reading that sets the driver's tolerance (`PERF.md`): float8
    (e4m3, scaled to the tensor's largest value) wherever the
    configuration has bfloat16 — the weights, each block's normed input,
    the normed and rotated queries, the key and value rows a cache would
    hold, the attention's and a feed-forward's inner result, and the
    residual stream after each block. `fault` plants one of `FAULTS` in
    every layer's attention."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    assert fault is None or fault in FAULTS, fault

    f32 = jnp.float32
    eps = sizes["rms_norm_eps"]
    nh, hkv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    group = nh // hkv
    top_e, first = sizes["num_experts_per_tok"], sizes["first_expert"]
    dense_layers = sizes["num_dense_layers"]
    route_scale = float(sizes["route_scale"])
    theta = float(sizes["rope_theta"])
    window = int(sizes["sliding_window"])
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
        """fn over `rows` query rows at a time: per_row arrays (T, ...)
        -> (T, ...)."""
        pad = blocks * rows - t
        cut = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (blocks, rows) + a.shape[1:]) for a in per_row]
        out = lax.map(lambda a: fn(*a), tuple(cut))
        return out.reshape((blocks * rows,) + out.shape[2:])[:t]

    def attention(p, u, full):
        q = rms((u @ up(p["q"])).reshape(t, nh, d), p["q_norm"])
        k = rms((u @ up(p["k"])).reshape(t, hkv, d), p["k_norm"])
        v = act((u @ up(p["v"])).reshape(t, hkv, d))   # what a cache holds
        gate = jax.nn.sigmoid(u @ up(p["gate"]))
        if not full or fault == "rope_on_global":
            q, k = turn(q), turn(k)
        q, k = act(q), act(k)
        sliding = not full and fault != "window_ignored"

        def block(q_b, at_b):
            s = jnp.arange(t)[None, :]
            seen = s <= at_b[:, None]                          # (R, T)
            if sliding:
                seen = seen & (s > at_b[:, None] - window)
            # query head h reads KV head h // group
            a = jnp.einsum("qhd,khd->hqk", q_b, jnp.repeat(k, group, 1)) \
                / jnp.sqrt(float(d))
            prob = jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", prob,
                              jnp.repeat(v, group, 1)).reshape(-1, nh * d)

        o = by_blocks(block, q, jnp.arange(t))
        if fault != "gate_dropped":
            o = o * gate
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
        if sizes["mup"]:
            x = act(x * jnp.sqrt(float(sizes["hidden_size"])))
        for li, p in enumerate(params["layers"]):
            a = attention(p, rms(x, p["norm_in"], True), sizes["full"][li])
            x = act(x + rms(a, p["norm_post_attn"]))
            g = rms(x, p["norm_pre_mlp"], True)
            f = swiglu(g, p["d_gate"], p["d_up"], p["d_down"]) \
                if li < dense_layers else moe(p, g)
            x = act(x + rms(f, p["norm_post_mlp"]))
        if where is not None:
            x = x[where]
        return rms(x, params["norm_f"], True) @ up(params["head"])

    if at is None:
        return lax.map(lambda row: sequence(row, None), ids)
    return lax.map(lambda a: sequence(*a), (ids, at))


def build(config, seed):
    return Built(config, seed)
