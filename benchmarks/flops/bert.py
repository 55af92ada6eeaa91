"""Operations of the ``bert`` family's training step, from shapes."""
from . import transformer as T

FLASH_KERNEL = "flash_attention_fwd"     # the kernel's stable name


def forward_flops(cfg, seq, valid_lens):
    """One forward pass over ``len(valid_lens)`` rows of ``seq``
    positions: every position goes through the layers, the MLM transform
    and the decoder over the vocabulary; the pooler and the NSP head see
    one position a row; attention produces every query row against the
    row's valid keys only, as the kernel's count below has it."""
    u, h = cfg["hidden_size"], cfg["intermediate_size"]
    layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    per_token = layers * T.layer_matmul_flops_per_token(u, h) \
        + 2 * u * u + 2 * u * vocab
    attention = sum(T.attention_flops(seq, int(n), u) for n in valid_lens)
    return len(valid_lens) * (seq * per_token + 2 * u * u + 4 * u) \
        + layers * attention


def train_step(cfg, traffic, batch):
    """Forward and backward of one step on the host batch ``(x, y)``: three
    times the forward (the backward needs two matmuls for each of the
    forward's)."""
    return 3 * forward_flops(cfg, traffic["seq"], batch[0][2])


def flash_fwd_per_step(cfg, traffic, batch):
    """(operations, bytes) the flash forward kernel needs in one step on
    the host batch ``(x, y)``, over all layers: every query row is produced,
    against the row's valid keys only.  float32 in and out."""
    u, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    seq = traffic["seq"]
    valid_lens = batch[0][2]
    ops = sum(T.attention_flops(seq, int(n), u) for n in valid_lens)
    byts = sum(T.attention_fwd_bytes(seq, int(n), u, 4) for n in valid_lens)
    return layers * ops, layers * byts
