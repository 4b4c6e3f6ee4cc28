"""The operations and bytes the algorithms need, computed from shapes.
Kept with the benchmark so that no later PR can change the yardstick.
A multiply-add counts as TWO floating-point operations."""
from __future__ import annotations

#: ResNet-50 (He et al., arXiv:1512.03385, table 1, 50-layer column):
#: (bottleneck widths, blocks, stride of the stage's first block)
RESNET50_STAGES = (((64, 64, 256), 3, 1), ((128, 128, 512), 4, 2),
                   ((256, 256, 1024), 6, 2), ((512, 512, 2048), 3, 2))


def _conv_macs(h, w, k, c_in, c_out, stride):
    """Multiply-adds of a 'same' convolution; returns (macs, h_out, w_out)."""
    ho, wo = -(-h // stride), -(-w // stride)
    return ho * wo * k * k * c_in * c_out, ho, wo


def resnet50_forward_flops(height, width, channels=3, classes=1000):
    """Forward FLOPs of one sample through the convolutions and the dense
    layer of ResNet-50 as the paper's table lays it out and the zoo builds
    it (v1: a stage's stride sits on the first 1x1 convolution of its first
    block, and on that block's projection shortcut). Batch norm, pooling
    and activations are not counted."""
    macs, h, w = _conv_macs(height, width, 7, channels, 64, 2)
    h, w = -(-h // 2), -(-w // 2)                 # 3x3 max pool, stride 2
    c_in = 64
    for (f1, f2, f3), blocks, stride in RESNET50_STAGES:
        for b in range(blocks):
            s = stride if b == 0 else 1
            if b == 0:                            # projection shortcut
                macs += _conv_macs(h, w, 1, c_in, f3, s)[0]
            m, h, w = _conv_macs(h, w, 1, c_in, f1, s)
            macs += m
            macs += _conv_macs(h, w, 3, f1, f2, 1)[0]
            macs += _conv_macs(h, w, 1, f2, f3, 1)[0]
            c_in = f3
    macs += c_in * classes
    return 2 * macs


def resnet50_train_flops(height, width, channels=3, classes=1000):
    """Forward plus backward: the backward pass computes a gradient for the
    input and one for the weights of every layer, twice the forward."""
    return 3 * resnet50_forward_flops(height, width, channels, classes)


def bert_weight_bytes(model, bytes_per_param=4):
    """Bytes of the parameters a decode step reads: every layer's matrices
    and vectors, the embedding tables (the word table is read whole by the
    tied output projection), and the language-model head."""
    h, i = model["hidden_size"], model["intermediate_size"]
    layer = (h * 3 * h + 3 * h) + (h * h + h) + 4 * h \
        + (h * i + i) + (i * h + h)
    emb = (model["vocab_size"] + model["max_position_embeddings"]) * h + 2 * h
    head = h * h + h + 2 * h + model["vocab_size"]
    return bytes_per_param * (model["num_hidden_layers"] * layer + emb + head)


def bert_kv_bytes_per_position(model, bytes_per_value=4):
    """Bytes of keys and values one cached position takes, all layers."""
    return 2 * model["num_hidden_layers"] * model["hidden_size"] \
        * bytes_per_value


def bert_decode_step_bytes(model, mean_rows_in_use, slots_in_use,
                           bytes_per_value=4):
    """The least bytes one decode step of the whole batch must move: the
    weights once, and the cache rows each occupied slot really holds."""
    return bert_weight_bytes(model, bytes_per_value) \
        + slots_in_use * mean_rows_in_use \
        * bert_kv_bytes_per_position(model, bytes_per_value)
