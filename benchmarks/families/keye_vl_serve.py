"""A Keye-VL-2.0 language model (grouped-query attention under a learned
sparse-attention indexer, M-RoPE, softmax-routed SwiGLU experts) behind
`KeyeDecoder` and `GenerationServer`: what the serving drivers need, built
from a configuration file's sizes and `--seed`, with the configuration's
copy of the plain reference beside it.

The file holds the source's `config.json` keys at its top level, with the
ones the cut changes (`reduced`) at the values held here and the published
ones under `published`; `held` says which layers, experts and vocabulary
rows this chip has."""
from __future__ import annotations

import os


def model_config(config, dtype):
    """The program's configuration from the file: the router keeps its
    published width, the experts held are the file's."""
    from deeplearning4j_tpu.models.keye_vl import KeyeVLConfig
    first = int(config["held"]["experts"][0])
    return KeyeVLConfig.from_dict(
        config, num_experts=int(config["published"]["num_experts"]),
        experts_held=(first, int(config["num_experts"])), dtype=dtype)


class Built:
    def __init__(self, config, seed):
        import jax

        from deeplearning4j_tpu.models.keye_vl import init_params

        s = config["serving"]
        self.config = config
        self.seed = int(seed)
        self.cfg = model_config(config, s["dtype"])
        self.slots = int(s["slots"])
        self.vocab = int(config["vocab_size"])      # the slice held here
        # the chip's own bit generator, as the hybrid's family uses it
        key = jax.random.fold_in(
            jax.random.key(self.seed & 0x7FFFFFFF, impl="rbg"),
            self.seed >> 31)
        # every weight on the device in one jitted call
        self.params = jax.block_until_ready(
            jax.jit(lambda k: init_params(self.cfg, k))(key))
        self._reference = {}        # lower -> the jitted reference

    def make_server(self, exec_cache_dir, max_new_tokens):
        """The server with the configuration's slots, rungs and buckets and
        NOTHING else named: every scheduler option stays at the program's
        default, so that a PR which changes a default is measured."""
        from deeplearning4j_tpu.generation.decode import KeyeDecoder
        from deeplearning4j_tpu.generation.server import GenerationServer

        s = self.config["serving"]
        os.makedirs(exec_cache_dir, exist_ok=True)
        return GenerationServer(
            KeyeDecoder(self.cfg, self.params), slots=self.slots,
            cache_lengths=list(s["cache_lengths"]),
            prompt_buckets=list(s["prompt_buckets"]),
            max_new_tokens=max_new_tokens, seed=self.seed & 0x7FFFFFFF,
            exec_cache_dir=exec_cache_dir)

    def reference_logits(self, ids, at=None, lower=False):
        """Next-token logits by the plain reference over the served
        weights: (..., T, vocab) at every position of `ids` ((T,) or
        (batch, T); the model is causal, so rows padded on the right are
        right up to their length), or (..., n, vocab) at the positions
        `at` ((n,) or (batch, n)) alone."""
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
        fn = self._reference.setdefault(lower, jax.jit(
            lambda p, x, a: reference_logits(p, x, sizes, at=a,
                                             lower=lower)))
        with jax.default_matmul_precision("highest"):
            out = fn(self.params, rows, where)
        out = np.asarray(out, np.float32)
        return out if ids.ndim == 2 else out[0]


def reference_sizes(config):
    """What the reference needs of a configuration file, as plain numbers
    (it shares no code with `models/keye_vl.py`)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts_per_tok", "rms_norm_eps", "rope_theta")
    sizes = {k: config[k] for k in keys}
    sa = config["sa_config"]
    sizes.update(indexer_heads=int(sa["indexer_num_heads"]),
                 indexer_dim=int(sa["indexer_head_dim"]),
                 topk=int(sa["topk"]),
                 mrope_section=list(config["rope_scaling"]["mrope_section"]),
                 first_expert=int(config["held"]["experts"][0]))
    return sizes


#: query rows the reference's attention and indexer take at once (their
#: scores are heads x rows x T float32: 268 MB at 32 heads and T 16392)
REFERENCE_ROWS = 128


def reference_logits(params, ids, sizes, positions=None, at=None,
                     lower=False):
    """The plain reference: the model's forward as ISSUE 35 writes its
    equations (the source's `config.json`; DeepSeek-V3.2's indexer;
    Qwen3-MoE's q/k-norm and router), float32 `jax.numpy`, no kernel, no
    cache, no grouped product, the k best index scores by a SORT. (batch,
    time) ids -> (batch, time, vocab) logits, or (batch, n, vocab) at the
    positions `at` (batch, n). `positions` (3, time): the three rotary
    streams, by default the token's index in each.

    Every layer is `h = x + attn(RMSNorm(x))`, `y = h + moe(RMSNorm(h))`.
    Attention and the indexer run over `REFERENCE_ROWS` query rows at a
    time against ALL keys (full rows of scores, a mask, one softmax), the
    rows of a batch one after the other: a split of the work, not of the
    mathematics. The routed experts are a loop over the experts HELD here,
    each over every token under a mask (what the experts held elsewhere
    would add is left out, as in the program; the weights are normalised
    over all the chosen). The tree is the served one; a layer (an expert)
    is upcast as it is used.

    `lower` computes one precision below what the configuration states,
    for the reading that sets the driver's tolerance (`PERF.md`): float8
    (e4m3, scaled to the tensor's largest value) wherever the
    configuration has bfloat16 — the weights, each block's normed input,
    the rotated queries, the K, V and index-key rows a cache would hold,
    the attention's and an expert's inner result, and the residual stream
    after each block — and the index scores' products and sums in bfloat16
    where it has float32 sums."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    eps = sizes["rms_norm_eps"]
    hq, hkv, hd = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    nj, di, topk = sizes["indexer_heads"], sizes["indexer_dim"], \
        sizes["topk"]
    top_e, first = sizes["num_experts_per_tok"], sizes["first_expert"]
    theta = float(sizes["rope_theta"])
    t = ids.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (3, t))
    pos = positions.astype(f32)
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

    def turn(x, angle):
        """Rotate-half rotary of heads x (T, heads, W) by angle (T, W/2)."""
        cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
        a, b = x[..., :x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    # frequency i of the attention turns by the position of ITS stream
    stream = [c for c, n in enumerate(sizes["mrope_section"])
              for _ in range(n)]
    angle = jnp.stack([pos[c] for c in stream], -1) \
        * theta ** (-jnp.arange(hd // 2, dtype=f32) / (hd // 2))
    angle_i = pos[0][:, None] \
        * theta ** (-jnp.arange(di // 2, dtype=f32) / (di // 2))

    def by_blocks(fn, *per_row):
        """fn over `rows` query rows at a time: per_row arrays (T, ...)
        -> (T, ...)."""
        pad = blocks * rows - t
        cut = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (blocks, rows) + a.shape[1:]) for a in per_row]
        out = lax.map(lambda a: fn(*a), tuple(cut))
        return out.reshape((blocks * rows,) + out.shape[2:])[:t]

    def attention(p, u):
        q = rms((u @ up(p["q"])).reshape(t, hq, hd), p["q_norm"])
        k = rms((u @ up(p["k"])).reshape(t, hkv, hd), p["k_norm"])
        v = (u @ up(p["v"])).reshape(t, hkv, hd)
        q, k, v = act(turn(q, angle)), act(turn(k, angle)), act(v)
        qi = turn((u @ up(p["iq"])).reshape(t, nj, di), angle_i)
        ki = u @ up(p["ik"])
        ki = ki - jnp.mean(ki, -1, keepdims=True)
        ki = ki * lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True) + eps) \
            * p["ik_norm"] + p["ik_bias"]
        qi, ki = act(qi), act(turn(ki[:, None], angle_i)[:, 0])
        w = u @ up(p["iw"])                                   # (T, J)
        k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
        if lower:
            qi, ki = (a.astype(jnp.bfloat16) for a in (qi, ki))

        def block(q_b, qi_b, w_b, at_b):
            seen = jnp.arange(t)[None, :] <= at_b[:, None]    # (R, T)
            s = jnp.einsum("qjd,kd->qjk", qi_b, ki)
            s = (w_b[..., None].astype(s.dtype)
                 * jnp.maximum(s, 0)).sum(1).astype(f32)
            index = jnp.where(seen, (nj * di) ** -0.5 * s, -jnp.inf)
            # a key's rank among the row's scores, best first, ties to
            # the lower position (a stable sort)
            order = jnp.argsort(-index, axis=-1, stable=True)
            rank = jnp.argsort(order, axis=-1, stable=True)
            chosen = seen & (rank < jnp.minimum(topk, at_b + 1)[:, None])
            a = jnp.einsum("qhd,khd->hqk", q_b, k) / jnp.sqrt(float(hd))
            prob = jax.nn.softmax(jnp.where(chosen, a, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", prob, v).reshape(-1, hq * hd)

        return act(by_blocks(block, q, qi, w, jnp.arange(t))) @ up(p["o"])

    def moe(p, g):
        prob = jax.nn.softmax(g @ up(p["router"]), axis=-1)   # (T, E)
        val, idx = lax.top_k(prob, top_e)
        wts = val / val.sum(-1, keepdims=True)

        def expert(acc, inp):
            j, wg, wu, wd = inp
            w_tok = jnp.sum(jnp.where(idx == first + j, wts, 0.0), -1)
            y = act(jax.nn.silu(g @ up(wg)) * (g @ up(wu))) @ up(wd)
            return acc + w_tok[:, None] * y, None

        out, _ = lax.scan(expert, jnp.zeros_like(g), (
            jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
            p["w_down"]))
        return out

    def sequence(row, where):
        x = up(params["embed"])[row]
        for p in params["layers"]:
            x = act(x + attention(p, rms(x, p["norm1"], True)))
            x = act(x + moe(p, rms(x, p["norm2"], True)))
        if where is not None:
            x = x[where]
        return rms(x, params["norm_f"], True) @ up(params["head"])

    if at is None:
        return lax.map(lambda row: sequence(row, None), ids)
    return lax.map(lambda a: sequence(*a), (ids, at))


def build(config, seed):
    return Built(config, seed)
