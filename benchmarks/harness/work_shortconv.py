"""The bytes and the operations an LFM2-MoE language model (gated
short-convolution layers beside grouped-query attention layers, leading
dense layers, routed experts with no shared one, a head tied to the
embedding) must move and make: one decode step, from the configuration
file's sizes (`benchmarks/configs/`, the source's keys at the top level),
the cell's slots and what the program counted on the device
(`global_rows_attended`, `moe_expert_reads`); and the conv mixers and the
expert layers of a prompt, from its length. Kept with the benchmark so that no later PR can
change the yardstick. Weights, cached rows and conv tails are bfloat16 (2
bytes): what the configuration states.

Counted in what the mathematics needs (a slot's rows in use, a held expert
with at least one pair, a prompt's real tokens), not what an
implementation fetches or computes, so that the shares read the same work
whatever implements them. Activations (48 rows of a few thousand lanes a
projection), the step's new K/V row a slot, norm weights, the expert bias
and the embedding's rows of the step's tokens are left out: under 1 % of
any figure here at the published widths."""
from __future__ import annotations

W = 2            # bytes of a weight, a cached value or a tail's value


def layers_of(config):
    return int(config["num_hidden_layers"])


def dense_layers_of(config):
    return min(int(config["num_dense_layers"]), layers_of(config))


def conv_layers_of(config):
    """The layers held that are gated short convolutions (the rest
    attend)."""
    return sum(kind == "conv"
               for kind in config["layer_types"][:layers_of(config)])


def attention_layers_of(config):
    return layers_of(config) - conv_layers_of(config)


def kv_width(config):
    h = config["hidden_size"]
    return config["num_key_value_heads"] * h // config["num_attention_heads"]


def kv_row_bytes(config):
    """One cached position, one attention layer: its key and its value
    over the KV heads."""
    return 2 * kv_width(config) * W


def conv_params(config):
    """W_in (hidden -> 3 x hidden), W_out and the taps, one layer."""
    h = config["hidden_size"]
    return 4 * h * h + h * config["conv_L_cache"]


def attention_params(config):
    """W_q, W_k, W_v and W_o, one layer."""
    h = config["hidden_size"]
    return 2 * h * h + 2 * h * kv_width(config)


def expert_params(config):
    """One routed expert: gate and up (hidden -> width), down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def router_params(config):
    """The router over all the experts, one layer."""
    return config["hidden_size"] * config["num_experts"]


def dense_ffn_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def head_params(config):
    """The head, which is the embedding (tied): read whole a step."""
    return config["hidden_size"] * config["vocab_size"]


def tail_bytes(config, slots):
    """Every slot's tail of every conv layer, read and written once a
    step: K-1 rows of hidden each."""
    return 2 * conv_layers_of(config) * slots \
        * (config["conv_L_cache"] - 1) * config["hidden_size"] * W


def prefill_conv_work(config, prompt_len):
    """(operations, bytes) the conv mixers of one prompt need at least:
    each token through W_in and W_out (2 x 4 x hidden^2 FLOP) and the taps
    (2 x K x hidden, and hidden for the gate C), over the conv layers held;
    each layer's weights read once, its input read and its output written
    once (the mixers' least traffic)."""
    h, t = config["hidden_size"], int(prompt_len)
    flops = conv_layers_of(config) * t * (8 * h * h
                                          + (2 * config["conv_L_cache"] + 1)
                                          * h)
    nbytes = W * conv_layers_of(config) * (conv_params(config) + 2 * t * h)
    return flops, nbytes


def moe_step_bytes(config, expert_reads_per_step):
    """What the expert layers of one decode step must read: each one's
    router, and every (layer, held expert) with at least one pair once —
    the program's `moe_expert_reads` a step."""
    return W * ((layers_of(config) - dense_layers_of(config))
                * router_params(config)
                + expert_reads_per_step * expert_params(config))


def rows_bytes(config, rows_attended_per_step):
    """What attention must read: the K and V row of every position in
    use, slots and layers summed — the program's `global_rows_attended` a
    step."""
    return rows_attended_per_step * kv_row_bytes(config)


def resident_params(config):
    """What every slot's token passes through a step whatever it is routed
    to: the mixers, the dense layers' feed-forward, the expert layers'
    routers, the head."""
    dense = dense_layers_of(config)
    return conv_layers_of(config) * conv_params(config) \
        + attention_layers_of(config) * attention_params(config) \
        + dense * dense_ffn_params(config) \
        + (layers_of(config) - dense) * router_params(config) \
        + head_params(config)


def decode_step_bytes(config, slots, global_rows, expert_reads_per_step):
    """The least one decode step of the whole batch must move: the rows
    in use of the attention layers, every slot's conv tails, every weight
    outside the routed experts once, and every (layer, held expert) with
    at least one pair once."""
    return rows_bytes(config, global_rows) + tail_bytes(config, slots) \
        + W * (resident_params(config)
               + expert_reads_per_step * expert_params(config))


def prefill_moe_flops(config, prompt_len):
    """The expert layers of one prompt: each of its tokens' top-k pairs
    through an expert's three products (2 x 3 x hidden x width), over the
    expert layers held."""
    return int(prompt_len) * config["num_experts_per_tok"] \
        * (layers_of(config) - dense_layers_of(config)) \
        * 6 * config["hidden_size"] * config["moe_intermediate_size"]
