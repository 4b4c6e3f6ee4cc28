"""The bytes one decode step of a Nemotron-H hybrid must move, computed
from the configuration file's sizes (`benchmarks/configs/`, the source's
keys at the top level). Kept with the benchmark so that no later PR can
change the yardstick. Weights and activations are bfloat16 (2 bytes), the
Mamba-2 state float32 (4 bytes): what the configuration states.

Activations (S rows of a few thousand lanes a projection) are left out:
under 1 % of any figure here at the published widths."""
from __future__ import annotations

W = 2            # bytes of a weight, a KV value or a convolution-tail value
STATE = 4        # bytes of a Mamba-2 state value


def layers_of(config, kind):
    return config["held"]["pattern"].count(kind)


def expert_bytes(config):
    """One routed expert: latent -> width -> latent, no gate."""
    return 2 * config["moe_latent_size"] * config["moe_intermediate_size"] * W


def moe_fixed_bytes(config):
    """What one expert layer reads every step whatever the routing: the
    router over ALL published experts, the two latent projections and the
    shared expert."""
    h = config["hidden_size"]
    return W * (h * config["published"]["n_routed_experts"]
                + 2 * h * config["moe_latent_size"]
                + 2 * h * config["moe_shared_expert_intermediate_size"])


def moe_step_bytes(config, expert_reads_per_step):
    """All expert layers, one step: the fixed part of each, and every
    (layer, held expert) with at least one pair read once — the program's
    `moe_expert_reads` counter a step."""
    return layers_of(config, "E") * moe_fixed_bytes(config) \
        + expert_reads_per_step * expert_bytes(config)


def ssm_layer_bytes(config, slots):
    """One Mamba-2 layer, one step: every slot's state read and written,
    its convolution tail read and written, and the mixer's weights."""
    nh, hd = config["mamba_num_heads"], config["mamba_head_dim"]
    n, h = config["ssm_state_size"], config["hidden_size"]
    d_inner = nh * hd
    conv_dim = d_inner + 2 * config["n_groups"] * n
    state = 2 * slots * nh * hd * n * STATE
    tail = 2 * slots * (config["conv_kernel"] - 1) * conv_dim * W
    weights = W * (h * (d_inner + conv_dim + nh) + d_inner * h
                   + conv_dim * config["conv_kernel"])
    return state + tail + weights


def ssm_step_bytes(config, slots):
    return layers_of(config, "M") * ssm_layer_bytes(config, slots)


def kv_bytes_per_position(config):
    """Keys and values of one cached position, all attention layers."""
    return layers_of(config, "*") * 2 * config["num_key_value_heads"] \
        * config["head_dim"] * W


def attention_weight_bytes(config):
    h = config["hidden_size"]
    qw = config["num_attention_heads"] * config["head_dim"]
    kvw = config["num_key_value_heads"] * config["head_dim"]
    return layers_of(config, "*") * W * (h * (qw + 2 * kvw) + qw * h)


def decode_step_bytes(config, slots, mean_rows_in_use,
                      expert_reads_per_step):
    """The least one decode step of the whole batch must move: the expert
    layers, the Mamba-2 layers, the attention layers' weights and the cache
    rows each slot really holds, and the head over the vocabulary held."""
    return moe_step_bytes(config, expert_reads_per_step) \
        + ssm_step_bytes(config, slots) \
        + attention_weight_bytes(config) \
        + slots * mean_rows_in_use * kv_bytes_per_position(config) \
        + W * config["hidden_size"] * config["vocab_size"]
