"""Operations and bytes of the ``mla_moe_lm`` family's training step, from
shapes.  A multiply-add counts as two operations.  What the algorithm needs
is counted once; recomputation is never counted in ``train_step``."""

FLASH_KERNEL = "flash_attention_fwd"     # the kernel's stable name


def _blocks(cfg):
    """(dense blocks, sparse-expert blocks), the MTP module's block among
    the sparse ones."""
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense \
        + cfg["num_nextn_predict_layers"]


def mla_projection_flops_per_token(cfg):
    u, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return 2 * (u * qr + qr * h * (nope + rope) + u * (kvr + rope)
                + kvr * h * (nope + vd) + h * vd * u)


def attention_core_flops(cfg, seq):
    """One sequence, one block, all heads: every query against its own
    prefix (seq (seq + 1) / 2 pairs), Q K^T over nope + rope lanes and
    P V over the value's."""
    pairs = seq * (seq + 1) // 2
    return cfg["num_attention_heads"] * pairs * 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def expert_layer_flops_per_token(cfg):
    """Router over all experts, the shared expert, and the routed experts
    held here at the expected top_k x held / routed assignments a token."""
    u, h = cfg["hidden_size"], cfg["moe_intermediate_size"]
    expected = cfg["num_experts_per_tok"] * cfg["n_routed_experts_held"] \
        / cfg["n_routed_experts"]
    return 2 * u * cfg["n_routed_experts"] \
        + (cfg["n_shared_experts"] + expected) * 2 * 3 * u * h


def forward_flops(cfg, batch, seq):
    u, vocab = cfg["hidden_size"], cfg["vocab_size"]
    dense, sparse = _blocks(cfg)
    mtp = cfg["num_nextn_predict_layers"]
    per_token = (dense + sparse) * mla_projection_flops_per_token(cfg) \
        + dense * 2 * 3 * u * cfg["intermediate_size"] \
        + sparse * expert_layer_flops_per_token(cfg) \
        + (1 + mtp) * 2 * u * vocab + mtp * 2 * 2 * u * u
    return batch * (seq * per_token
                    + (dense + sparse) * attention_core_flops(cfg, seq))


def train_step(cfg, traffic, batch):
    """Forward and backward of one step: three times the forward."""
    return 3 * forward_flops(cfg, traffic["batch"], traffic["seq"])


def flash_fwd_per_step(cfg, traffic, batch):
    """(operations, bytes) of the flash forward kernel's calls in one step,
    over all blocks: each block's call and, the blocks being
    rematerialised, the same call again in the backward (the kernel runs
    twice; its share of the roofline is per call).  Read Q, K and V once,
    write the output once, float32."""
    dense, sparse = _blocks(cfg)
    seq, rows = traffic["seq"], traffic["batch"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    calls = 2 * (dense + sparse) * rows
    byts = 4 * seq * cfg["num_attention_heads"] * width * 4
    return calls * attention_core_flops(cfg, seq), calls * byts
