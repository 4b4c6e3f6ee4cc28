"""BERT (causal) behind `BertDecoder` and `GenerationServer`: what the
serving drivers need, built from a configuration file's sizes and
`--seed`, with the plain reference forward beside it."""
from __future__ import annotations

import os


class Built:
    def __init__(self, config, seed):
        import jax

        from deeplearning4j_tpu.models.bert import (BertConfig,
                                                    init_bert_params)

        m, s = config["model"], config["serving"]
        self.config = config
        self.model = m
        self.seed = int(seed)
        self.cfg = BertConfig(
            vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
            num_layers=m["num_hidden_layers"],
            num_heads=m["num_attention_heads"],
            intermediate_size=m["intermediate_size"],
            max_position_embeddings=m["max_position_embeddings"],
            type_vocab_size=m["type_vocab_size"],
            layer_norm_eps=m["layer_norm_eps"], dtype=s["dtype"])
        self.slots = int(s["slots"])
        self.vocab = int(m["vocab_size"])
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed & 0x7FFFFFFF),
                                 self.seed >> 31)
        # every weight on the device in one jitted call
        self.params = jax.block_until_ready(jax.jit(
            lambda k: init_bert_params(self.cfg, k))(key))

    def make_server(self, exec_cache_dir, max_new_tokens):
        """The server with the configuration's slots, rungs and buckets and
        NOTHING else named: every scheduler option stays at the program's
        default, so that a PR which changes a default is measured."""
        from deeplearning4j_tpu.generation.decode import BertDecoder
        from deeplearning4j_tpu.generation.server import GenerationServer

        s = self.config["serving"]
        os.makedirs(exec_cache_dir, exist_ok=True)
        return GenerationServer(
            BertDecoder(self.cfg, self.params), slots=self.slots,
            cache_lengths=list(s["cache_lengths"]),
            prompt_buckets=list(s["prompt_buckets"]),
            max_new_tokens=max_new_tokens, seed=self.seed & 0x7FFFFFFF,
            exec_cache_dir=exec_cache_dir)

    def reference_last_logits(self, prompt):
        """Next-token logits after `prompt` by the plain reference."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        ids = jnp.asarray(np.asarray(prompt, np.int32))[None]
        with jax.default_matmul_precision("highest"):
            logits = jax.jit(lambda p, x: reference_logits(
                p, x, self.cfg.num_heads, self.cfg.layer_norm_eps))(
                    self.params, ids)
        return np.asarray(logits, np.float32)[0, -1]


def _layer_norm(x, scale, bias, eps):
    import jax
    import jax.numpy as jnp
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def reference_logits(params, ids, num_heads, eps):
    """The plain reference: BERT's published forward (Devlin et al.,
    arXiv:1810.04805; post-layer-norm blocks, learned positions, tied
    output embedding) with a causal mask, in float32 `jax.numpy` with no
    kernel, cache or batching. (batch, time) ids -> (batch, time, vocab).

    Departures from the published model, both the program's own: the
    causal mask (the program serves BERT as a left-to-right decoder, token
    types unused), and GELU in its tanh form (`jax.nn.gelu`'s default)
    where the published checkpoint uses the erf form."""
    import jax
    import jax.numpy as jnp

    emb = params["embeddings"]
    b, t = ids.shape
    x = emb["word"][ids] + emb["position"][None, :t]
    x = _layer_norm(x, emb["ln_scale"], emb["ln_bias"], eps)
    h = x.shape[-1]
    d = h // num_heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    for layer in params["layers"]:
        qkv = x @ layer["qkv_W"] + layer["qkv_b"]
        q, k, v = (a.reshape(b, t, num_heads, d).transpose(0, 2, 1, 3)
                   for a in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, h)
        x = _layer_norm(x + ctx @ layer["proj_W"] + layer["proj_b"],
                        layer["ln1_scale"], layer["ln1_bias"], eps)
        f = layer["ffn"]
        up = jax.nn.gelu(x @ f["up_W"] + f["up_b"], approximate=True)
        x = _layer_norm(x + up @ f["down_W"] + f["down_b"],
                        layer["ln2_scale"], layer["ln2_bias"], eps)
    m = params["mlm_head"]
    y = jax.nn.gelu(x @ m["W"] + m["b"], approximate=True)
    y = _layer_norm(y, m["ln_scale"], m["ln_bias"], 1e-12)
    return y @ emb["word"].T + m["out_bias"]


def build(config, seed):
    return Built(config, seed)
