"""Operations and bytes of the ``gdn_hybrid_lm`` family's training step, from
shapes.  A multiply-add counts as two operations.  What the algorithm needs
is counted once: recomputation is never counted, and the delta rule is
counted by its recurrence, not by what a chunked form spends on it."""

FLASH_KERNEL = "flash_attention_fwd"     # the kernel's stable name
LINEAR, FULL = "linear_attention", "full_attention"


def _kinds(cfg):
    """(delta-rule blocks, attention blocks)."""
    kinds = cfg["layer_types"]
    return kinds.count(LINEAR), kinds.count(FULL)


def gdn_projection_flops_per_token(cfg):
    """q, k, v, the output gate, the output projection, a and b."""
    u, h = cfg["hidden_size"], cfg["linear_num_value_heads"]
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = h * cfg["linear_value_head_dim"]
    return 2 * (2 * u * kd + 3 * u * vd + 2 * u * h)


def gdn_conv_flops_per_token(cfg):
    """Three depthwise convolutions of ``linear_conv_kernel_dim`` taps."""
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return 2 * cfg["linear_conv_kernel_dim"] * (2 * kd + vd)


def delta_rule_flops_per_token(cfg):
    """The recurrence, a token, all heads: read ``k^T S`` (2 dk dv), decay
    the state (dk dv), write one rank-one update (2 dk dv), read ``S^T q``
    (2 dk dv)."""
    return 7 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def attention_projection_flops_per_token(cfg):
    return 2 * 4 * cfg["hidden_size"] ** 2


def attention_core_flops(cfg, seq):
    """One sequence, one block, all heads: every query against its own
    prefix (seq (seq + 1) / 2 pairs), Q K^T and P V over the head's lanes."""
    pairs = seq * (seq + 1) // 2
    return pairs * 2 * 2 * cfg["hidden_size"]


def forward_flops(cfg, batch, seq):
    u = cfg["hidden_size"]
    linear, full = _kinds(cfg)
    per_token = linear * (gdn_projection_flops_per_token(cfg)
                          + gdn_conv_flops_per_token(cfg)
                          + delta_rule_flops_per_token(cfg)) \
        + full * attention_projection_flops_per_token(cfg) \
        + (linear + full) * 2 * 3 * u * cfg["intermediate_size"] \
        + 2 * u * cfg["vocab_size"]
    return batch * (seq * per_token + full * attention_core_flops(cfg, seq))


def train_step(cfg, traffic, batch):
    """Forward and backward of one step: three times the forward."""
    return 3 * forward_flops(cfg, traffic["batch"], traffic["seq"])


def flash_fwd_per_step(cfg, traffic, batch):
    """(operations, bytes) of the flash forward kernel's calls in one step:
    one call an attention block a row of the batch.  The blocks are
    rematerialised, and a rematerialised block keeps the kernel's output,
    so the backward does not run the kernel again.  Read Q, K and V once,
    write the output once, float32."""
    _, full = _kinds(cfg)
    seq = traffic["seq"]
    calls = full * traffic["batch"]
    byts = 4 * seq * cfg["hidden_size"] * 4
    return calls * attention_core_flops(cfg, seq), calls * byts
