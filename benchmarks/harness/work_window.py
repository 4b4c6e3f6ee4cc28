"""The bytes and the operations an AFMoE language model (sliding-window and
full grouped-query attention layers, a gated attention output, leading
dense layers, routed and shared experts) must move and make: one decode
step, from the configuration file's sizes (`benchmarks/configs/`, the
source's keys at the top level) and what the program counted on the device
(`window_rows_attended`, `global_rows_attended`, `moe_expert_reads`); and a
prompt's banded attention, from its length. Kept with the benchmark so that
no later PR can change the yardstick. Weights and cached rows are bfloat16
(2 bytes): what the configuration states.

Counted in the rows and pairs the mathematics needs (a slot's rows in use;
a query's keys inside its window), not what an implementation fetches or
computes, so that the shares read the same work whatever implements them.
Activations (48 rows of a few thousand lanes a projection), the step's new
K/V row a slot, norm weights and the router's bias are left out: under 1 %
of any figure here at the published widths."""
from __future__ import annotations

W = 2            # bytes of a weight or a cached value


def layers_of(config):
    return int(config["num_hidden_layers"])


def dense_layers_of(config):
    return min(int(config["num_dense_layers"]), layers_of(config))


def sliding_layers_of(config):
    """The layers held that attend a window (the rest attend everything)."""
    return sum(kind == "sliding_attention"
               for kind in config["layer_types"][:layers_of(config)])


def kv_row_bytes(config):
    """One cached position, one layer: its key and its value over the KV
    heads."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * W


def q_width(config):
    return config["num_attention_heads"] * config["head_dim"]


def attention_params(config):
    """W_q, W_k, W_v, the gate and W_o, one layer."""
    h = config["hidden_size"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return 3 * h * q_width(config) + 2 * h * kv


def expert_params(config):
    """One routed expert: gate and up (hidden -> width), down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_params(config):
    return config["num_shared_experts"] * expert_params(config)


def router_params(config):
    """The router over ALL published experts, one layer."""
    return config["hidden_size"] * config["published"]["num_experts"]


def dense_ffn_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def head_params(config):
    """The head over the vocabulary held (the embedding is a row a slot)."""
    return config["hidden_size"] * config["vocab_size"]


def resident_params(config):
    """What every slot's token passes through a step whatever it is routed
    to: attention, the dense layers' feed-forward, the expert layers'
    router and shared expert, the head."""
    dense = dense_layers_of(config)
    return layers_of(config) * attention_params(config) \
        + dense * dense_ffn_params(config) \
        + (layers_of(config) - dense) * (router_params(config)
                                         + shared_params(config)) \
        + head_params(config)


def moe_step_bytes(config, expert_reads_per_step):
    """What the expert layers of one decode step must read: each one's
    router and shared expert, and every (layer, held expert) with at least
    one pair once — the program's `moe_expert_reads` a step."""
    return W * ((layers_of(config) - dense_layers_of(config))
                * (router_params(config) + shared_params(config))
                + expert_reads_per_step * expert_params(config))


def rows_bytes(config, rows_attended_per_step):
    """What attention must read of a kind of layer: the K and V row of
    every position in use, slots and layers summed — the program's
    `window_rows_attended` or `global_rows_attended` a step."""
    return rows_attended_per_step * kv_row_bytes(config)


def decode_step_bytes(config, window_rows, global_rows,
                      expert_reads_per_step):
    """The least one decode step of the whole batch must move: the rows in
    use of both kinds of layer, every weight outside the routed experts
    once, and every (layer, held expert) with at least one pair once."""
    return rows_bytes(config, window_rows + global_rows) \
        + W * (resident_params(config)
               + expert_reads_per_step * expert_params(config))


def band_pairs(prompt_len, window):
    """(query, key) pairs of a causal window over a prompt of `prompt_len`:
    query t sees `min(t + 1, window)` keys."""
    p, w = int(prompt_len), int(window)
    inside = min(p, w)
    return inside * (inside + 1) // 2 + max(p - w, 0) * w


def prefill_window_flops(config, prompt_len):
    """The banded attention of one prompt over the sliding layers held:
    every pair's score and weighted sum, 2 x 2 x head_dim a query head."""
    return band_pairs(prompt_len, config["sliding_window"]) \
        * 4 * q_width(config) * sliding_layers_of(config)
