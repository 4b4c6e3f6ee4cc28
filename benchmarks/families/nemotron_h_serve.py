"""A Nemotron-H hybrid (Mamba-2, attention, LatentMoE) behind
`NemotronHDecoder` and `GenerationServer`: what the serving drivers need,
built from a configuration file's sizes and `--seed`, with the
configuration's copy of the plain reference beside it.

The file holds the source's `config.json` keys at its top level, with the
three that the cut changes (`reduced`) at the values held here and the
published ones under `published`; `held` says which layers, experts and
vocabulary rows this chip has."""
from __future__ import annotations

import os


def model_config(config, dtype):
    """The program's configuration from the file: the router keeps its
    published width, the experts held are the file's."""
    from deeplearning4j_tpu.models.nemotron_h import NemotronHConfig
    first = int(config["held"]["experts"][0])
    return NemotronHConfig.from_dict(
        config, pattern=config["held"]["pattern"],
        n_routed_experts=int(config["published"]["n_routed_experts"]),
        experts_held=(first, int(config["n_routed_experts"])),
        dtype=dtype)


class Built:
    def __init__(self, config, seed):
        import jax

        from deeplearning4j_tpu.models.nemotron_h import init_params

        s = config["serving"]
        self.config = config
        self.seed = int(seed)
        self.cfg = model_config(config, s["dtype"])
        assert len(self.cfg.pattern) == config["num_hidden_layers"]
        self.slots = int(s["slots"])
        self.vocab = int(config["vocab_size"])      # the slice held here
        # the chip's own bit generator: 4.65 G weights from threefry's
        # integer rounds would be most of set-up
        key = jax.random.fold_in(
            jax.random.key(self.seed & 0x7FFFFFFF, impl="rbg"),
            self.seed >> 31)
        # every weight on the device in one jitted call
        self.params = jax.block_until_ready(
            jax.jit(lambda k: init_params(self.cfg, k))(key))

    def make_server(self, exec_cache_dir, max_new_tokens):
        """The server with the configuration's slots, rungs and buckets and
        NOTHING else named: every scheduler option stays at the program's
        default, so that a PR which changes a default is measured."""
        from deeplearning4j_tpu.generation.decode import NemotronHDecoder
        from deeplearning4j_tpu.generation.server import GenerationServer

        s = self.config["serving"]
        os.makedirs(exec_cache_dir, exist_ok=True)
        return GenerationServer(
            NemotronHDecoder(self.cfg, self.params), slots=self.slots,
            cache_lengths=list(s["cache_lengths"]),
            prompt_buckets=list(s["prompt_buckets"]),
            max_new_tokens=max_new_tokens, seed=self.seed & 0x7FFFFFFF,
            exec_cache_dir=exec_cache_dir)

    def reference_logits(self, ids, lower=False):
        """(..., T, vocab) next-token logits at every position of `ids`
        ((T,) or (batch, T); the model is causal, so rows padded on the
        right are right up to their length) by the plain reference, over
        the served weights."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        sizes = reference_sizes(self.config)
        ids = jnp.asarray(np.asarray(ids, np.int32))
        with jax.default_matmul_precision("highest"):
            out = jax.jit(lambda p, x: reference_logits(
                p, x, sizes, lower))(self.params, jnp.atleast_2d(ids))
        out = np.asarray(out, np.float32)
        return out if ids.ndim == 2 else out[0]


def reference_sizes(config):
    """What the reference needs of a configuration file, as plain numbers
    (it shares no code with `models/nemotron_h.py`)."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "num_experts_per_tok",
            "routed_scaling_factor", "norm_eps")
    sizes = {k: config[k] for k in keys}
    sizes["pattern"] = config["held"]["pattern"]
    sizes["first_expert"] = int(config["held"]["experts"][0])
    return sizes


def reference_logits(params, ids, sizes, lower=False):
    """The plain reference: the model's forward as its equations are
    written (ISSUE 28; Nemotron-H, arXiv:2504.03624; Mamba-2,
    arXiv:2405.21060), float32 `jax.numpy`, no kernel, cache, chunking or
    grouped product. (batch, time) ids -> (batch, time, vocab) logits.

    Every layer is `x <- x + mixer(RMSNorm(x))`. The Mamba-2 scan is a
    sequential `lax.scan` over time; the convolution four shifted adds;
    attention a masked softmax with no position encoding; the routed
    experts a loop over the experts HELD here, each over every token under
    a mask (what the experts held elsewhere would add is left out, as in
    the program; the weights are normalised over all the chosen). The tree
    is the served one; a layer (an expert) is upcast as it is used.

    `lower` computes one precision below what the configuration states,
    for the reading that sets the driver's tolerance (`PERF.md`): weights
    and each mixer's input through float8 (e4m3, scaled to the tensor's
    largest value) where the configuration has bfloat16, the scan's state
    through bfloat16 where it has float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    eps = sizes["norm_eps"]
    hq, hkv, hd = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    nh, mhd = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    g, n, kk = sizes["n_groups"], sizes["ssm_state_size"], \
        sizes["conv_kernel"]
    top_k, first = sizes["num_experts_per_tok"], sizes["first_expert"]
    di = nh * mhd
    bsz, t = ids.shape

    def float8(v):
        scale = jnp.max(jnp.abs(v)) / 448.0 + 1e-30
        return (v / scale).astype(jnp.float8_e4m3fn).astype(f32) * scale

    def up(w):
        return float8(w.astype(f32)) if lower else w.astype(f32)

    def rms(x, w):
        u = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w
        return float8(u) if lower else u

    def relu2(v):
        return jnp.maximum(v, 0.0) ** 2

    def mamba(p, u):
        zxbcdt = u @ up(p["in_proj"])
        cd = di + 2 * g * n
        z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + cd],
                      zxbcdt[..., di + cd:])
        w = up(p["conv_w"])
        conv = p["conv_b"] + sum(
            w[:, j] * jnp.pad(xbc, ((0, 0), (kk - 1 - j, 0), (0, 0)))[:, :t]
            for j in range(kk))
        xbc = jax.nn.silu(conv)
        x = xbc[..., :di].reshape(bsz, t, nh, mhd)
        b = xbc[..., di:di + g * n].reshape(bsz, t, g, n)
        c = xbc[..., di + g * n:].reshape(bsz, t, g, n)
        b, c = (jnp.repeat(v, nh // g, axis=2) for v in (b, c))
        dt = jax.nn.softplus(dt + p["dt_bias"])              # (B, T, nh)
        a = -jnp.exp(p["A_log"])

        def step(h, inp):
            x_t, b_t, c_t, dt_t = inp
            h = jnp.exp(dt_t * a)[..., None, None] * h \
                + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
            if lower:
                h = h.astype(jnp.bfloat16).astype(f32)
            return h, jnp.sum(h * c_t[:, :, None, :], -1)

        _, y = lax.scan(step, jnp.zeros((bsz, nh, mhd, n), f32),
                        tuple(jnp.moveaxis(v, 1, 0)
                              for v in (x, b, c, dt)))
        y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * x
        y = y.reshape(bsz, t, di) * jax.nn.silu(z)
        y = y.reshape(bsz, t, g, di // g)
        y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return (y.reshape(bsz, t, di) * p["gate_norm"]) @ up(p["out_proj"])

    def attention(p, u):
        qkv = u @ up(p["qkv"])
        q = qkv[..., :hq * hd].reshape(bsz, t, hq, hd)
        k = qkv[..., hq * hd:(hq + hkv) * hd].reshape(bsz, t, hkv, hd)
        v = qkv[..., (hq + hkv) * hd:].reshape(bsz, t, hkv, hd)
        k, v = (jnp.repeat(a, hq // hkv, axis=2) for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
        causal = jnp.tril(jnp.ones((t, t), bool))
        prob = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", prob, v)
        return ctx.reshape(bsz, t, hq * hd) @ up(p["o"])

    def moe(p, u):
        s = jax.nn.sigmoid(u @ up(p["router"]))              # (B, T, E)
        _, idx = lax.top_k(s + p["e_bias"], top_k)
        chosen = jnp.take_along_axis(s, idx, -1)
        wts = sizes["routed_scaling_factor"] * chosen \
            / (chosen.sum(-1, keepdims=True) + 1e-20)
        lat = u @ up(p["down"])

        def expert(acc, inp):
            j, w1, w2 = inp
            w_tok = jnp.sum(jnp.where(idx == first + j, wts, 0.0), -1)
            return acc + w_tok[..., None] * (relu2(lat @ up(w1))
                                             @ up(w2)), None

        routed, _ = lax.scan(
            expert, jnp.zeros_like(lat),
            (jnp.arange(p["w1"].shape[0]), p["w1"], p["w2"]))
        return routed @ up(p["up"]) \
            + relu2(u @ up(p["shared_w1"])) @ up(p["shared_w2"])

    mixers = {"M": mamba, "*": attention, "E": moe}
    x = up(params["embed"][ids])
    for kind, p in zip(sizes["pattern"], params["layers"]):
        x = x + mixers[kind](p, rms(x, p["norm"]))
    return rms(x, params["norm_f"]) @ up(params["head"])


def build(config, seed):
    return Built(config, seed)
