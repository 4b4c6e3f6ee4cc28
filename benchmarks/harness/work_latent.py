"""The bytes and the operations one decode step of a DeepSeek-V3 style
language model (multi-head latent attention in its absorbed form, a leading
dense layer, routed and shared experts) must move and make, computed from
the configuration file's sizes (`benchmarks/configs/`, the source's keys at
the top level) and what the program counted on the device
(`mla_rows_attended`, `moe_expert_reads`, `moe_pairs`). Kept with the
benchmark so that no later PR can change the yardstick. Weights and latent
rows are bfloat16 (2 bytes): what the configuration states.

Counted in the rows the mathematics needs (a slot's rows in use), not the
rows an implementation fetches, so that the shares read the same work
whatever implements it. Activations (48 rows of a few thousand lanes a
projection), the step's new latent row a slot, norm weights and the
router's bias are left out: under 1 % of any figure here at the published
widths."""
from __future__ import annotations

W = 2            # bytes of a weight or a cached value


def layers_of(config):
    return int(config["num_hidden_layers"])


def dense_layers_of(config):
    return min(int(config["first_k_dense_replace"]), layers_of(config))


def latent_row_bytes(config):
    """One cached position, one layer: the normed latent and the rotated
    key lanes, from which every head's key AND value come."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * W


def attention_row_flops(config):
    """One cached position, one layer, absorbed form: every head's score
    over latent + rotary lanes and its weighted sum over the latent."""
    return 2 * config["num_attention_heads"] * (
        2 * config["kv_lora_rank"] + config["qk_rope_head_dim"])


def attention_params(config):
    """W_q, W_kva, W_kvb (both halves: the absorbed query and the value
    up-projection use each once a token) and W_o, one layer."""
    h, nh = config["hidden_size"], config["num_attention_heads"]
    lat, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v = config["qk_nope_head_dim"], config["v_head_dim"]
    return h * nh * (nope + rope) + h * (lat + rope) \
        + lat * nh * (nope + v) + nh * v * h


def expert_params(config):
    """One routed expert: gate and up (hidden -> width), down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_params(config):
    """The shared experts as one SwiGLU of n_shared_experts x the width."""
    return config["n_shared_experts"] * expert_params(config)


def router_params(config):
    """The router over ALL published experts, one layer."""
    return config["hidden_size"] * config["published"]["n_routed_experts"]


def dense_ffn_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def head_params(config):
    """The head over the vocabulary held (the embedding is a row a slot)."""
    return config["hidden_size"] * config["vocab_size"]


def resident_params(config):
    """What every slot's token passes through a step whatever it is routed
    to: attention, the dense layers' feed-forward, the expert layers'
    router and shared experts, the head."""
    dense = dense_layers_of(config)
    return layers_of(config) * attention_params(config) \
        + dense * dense_ffn_params(config) \
        + (layers_of(config) - dense) * (router_params(config)
                                         + shared_params(config)) \
        + head_params(config)


def moe_step_bytes(config, expert_reads_per_step):
    """What the expert layers of one decode step must read: each one's
    router and shared experts, and every (layer, held expert) with at least
    one pair once — the program's `moe_expert_reads` a step."""
    return W * ((layers_of(config) - dense_layers_of(config))
                * (router_params(config) + shared_params(config))
                + expert_reads_per_step * expert_params(config))


def latent_rows_bytes(config, rows_attended_per_step):
    """What attention must read: the latent row of every position in use,
    slots and layers summed — the program's `mla_rows_attended` a step."""
    return rows_attended_per_step * latent_row_bytes(config)


def latent_rows_flops(config, rows_attended_per_step):
    return rows_attended_per_step * attention_row_flops(config)


def decode_step_bytes(config, rows_attended_per_step, expert_reads_per_step):
    """The least one decode step of the whole batch must move: the latent
    rows in use, every weight outside the routed experts once, and every
    (layer, held expert) with at least one pair once — the program's
    `moe_expert_reads` a step."""
    return latent_rows_bytes(config, rows_attended_per_step) \
        + W * (resident_params(config)
               + expert_reads_per_step * expert_params(config))


def decode_step_flops(config, rows_attended_per_step, pairs_per_step):
    """The operations one decode step of the whole batch needs: attention
    over the rows in use, every slot's token through the resident weights,
    and every (token, held expert) pair — the program's `moe_pairs` a
    step — through its expert."""
    slots = int(config["serving"]["slots"])
    return latent_rows_flops(config, rows_attended_per_step) \
        + 2 * (slots * resident_params(config)
               + pairs_per_step * expert_params(config))
