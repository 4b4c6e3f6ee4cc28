"""The bytes one decode step of a Keye-VL-2.0 language model must move,
computed from the configuration file's sizes (`benchmarks/configs/`, the
source's keys at the top level) and what the program counted on the device
(`dsa_rows_scored`, `dsa_rows_selected`, `moe_expert_reads`). Kept with the
benchmark so that no later PR can change the yardstick. Weights, cache rows
and index keys are bfloat16 (2 bytes): what the configuration states.

Activations (32 rows of a few thousand lanes a projection), the index
scores (a float32 a scored row) and the step's three new cache rows a slot
are left out: under 1 % of any figure here at the published widths."""
from __future__ import annotations

W = 2            # bytes of a weight, a cache value or an index-key value


def layers_of(config):
    return int(config["num_hidden_layers"])


def expert_bytes(config):
    """One routed expert: gate and up (hidden -> width), down (width ->
    hidden)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * W


def router_bytes(config):
    """The router over ALL published experts, one layer."""
    return config["hidden_size"] * config["published"]["num_experts"] * W


def moe_step_bytes(config, expert_reads_per_step):
    """All expert layers, one step: each layer's router, and every (layer,
    held expert) with at least one pair read once — the program's
    `moe_expert_reads` counter a step."""
    return layers_of(config) * router_bytes(config) \
        + expert_reads_per_step * expert_bytes(config)


def kv_row_bytes(config):
    """The K and the V row of one cached position, one layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * W


def index_key_bytes(config):
    """The index key of one cached position, one layer."""
    return config["sa_config"]["indexer_head_dim"] * W


def attention_weight_bytes(config):
    """q, k, v and o, one layer."""
    h = config["hidden_size"]
    qw = config["num_attention_heads"] * config["head_dim"]
    kvw = config["num_key_value_heads"] * config["head_dim"]
    return W * (h * (qw + 2 * kvw) + qw * h)


def indexer_weight_bytes(config):
    """The indexer's three projections, one layer: queries, the one key
    head, the heads' weights."""
    sa = config["sa_config"]
    return W * config["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def index_scan_bytes(config, rows_scored_per_step):
    """What scoring must read: the index key of every row in use, slots
    and layers summed — the program's `dsa_rows_scored` counter a step."""
    return rows_scored_per_step * index_key_bytes(config)


def selected_row_bytes(config, rows_selected_per_step):
    """What attention must read: the K and V rows the indexer kept, slots
    and layers summed — the program's `dsa_rows_selected` counter a
    step."""
    return rows_selected_per_step * kv_row_bytes(config)


def decode_step_bytes(config, rows_scored_per_step, rows_selected_per_step,
                      expert_reads_per_step):
    """The least one decode step of the whole batch must move: every
    layer's attention and indexer weights, the index keys of the rows in
    use, the K and V rows kept, the expert layers, and the head over the
    vocabulary held."""
    return layers_of(config) * (attention_weight_bytes(config)
                                + indexer_weight_bytes(config)) \
        + index_scan_bytes(config, rows_scored_per_step) \
        + selected_row_bytes(config, rows_selected_per_step) \
        + moe_step_bytes(config, expert_reads_per_step) \
        + W * config["hidden_size"] * config["vocab_size"]
