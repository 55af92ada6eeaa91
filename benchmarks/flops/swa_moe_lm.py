"""Operations and bytes of the ``swa_moe_lm`` family's training step, from
shapes.  A multiply-add counts as two operations.  What the algorithm needs
is counted once: recomputation is never counted, and a window block's
attention is counted by the pairs inside its window, not by the causal
triangle."""

FLASH_KERNEL = "flash_attention_fwd"     # the kernel's stable name ...
# ... and what the build with a window adds to it, forward and backward
FLASH_WINDOW_FWD = "flash_attention_fwd_window"
FLASH_WINDOW_BWD = ("flash_attention_bwd_dq_window",
                    "flash_attention_bwd_dkv_window")


def _kinds(cfg):
    """(window blocks, full blocks)."""
    windowed = sum(1 for w in cfg["sliding_window_layout"] if w)
    return windowed, len(cfg["sliding_window_layout"]) - windowed


def attention_projection_flops_per_token(cfg):
    """q and the output over all query heads, k and v over the key heads."""
    d = cfg["head_dim"]
    return 2 * cfg["hidden_size"] * d * (
        2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def attention_pairs(seq, window=None):
    """(query, key) pairs one head weighs over one sequence: key j on query
    i where j <= i and, with a window, i - j < window."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_core_flops(cfg, seq, window=None):
    """One sequence, one block, all query heads: Q K^T and P V over the
    head's lanes for every pair."""
    return cfg["num_attention_heads"] * attention_pairs(seq, window) \
        * 2 * 2 * cfg["head_dim"]


def expert_layer_flops_per_token(cfg):
    """Router over all experts and the routed experts held here at the
    expected top_k x held / routed assignments a token; no shared expert."""
    u, h = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    expected = cfg["moe_num_active_primary_experts"] \
        * cfg["n_routed_experts_held"] / cfg["moe_num_primary_experts"]
    return 2 * u * cfg["moe_num_primary_experts"] + expected * 2 * 3 * u * h


def forward_flops(cfg, batch, seq):
    windowed, full = _kinds(cfg)
    per_token = (windowed + full) * (
        attention_projection_flops_per_token(cfg)
        + expert_layer_flops_per_token(cfg)) \
        + 2 * cfg["hidden_size"] * cfg["vocab_size"]
    core = full * attention_core_flops(cfg, seq) + windowed \
        * attention_core_flops(cfg, seq, cfg["sliding_window_size"])
    return batch * (seq * per_token + core)


def train_step(cfg, traffic, batch):
    """Forward and backward of one step: three times the forward."""
    return 3 * forward_flops(cfg, traffic["batch"], traffic["seq"])


def _call_bytes(cfg, seq, gradients=False):
    """One call's HBM traffic, float32: q read and the output written (the
    backward: q and the cotangent read, dq written) a query head wide, k
    and v read (and dk, dv written) once a KEY head."""
    d = cfg["head_dim"]
    wide, narrow = cfg["num_attention_heads"] * d, \
        cfg["num_key_value_heads"] * d
    return 4 * seq * ((3 * wide + 4 * narrow) if gradients
                      else (2 * wide + 2 * narrow))


def flash_fwd_per_step(cfg, traffic, batch):
    """(operations, bytes) of the flash forward kernel's calls in one step,
    the window build's among them: one call a block a row of the batch.
    The blocks are rematerialised, and a rematerialised block keeps the
    kernel's output, so the backward does not run the kernel again."""
    windowed, full = _kinds(cfg)
    seq, rows = traffic["seq"], traffic["batch"]
    ops = full * attention_core_flops(cfg, seq) + windowed \
        * attention_core_flops(cfg, seq, cfg["sliding_window_size"])
    return rows * ops, rows * (windowed + full) * _call_bytes(cfg, seq)


def flash_window_fwd_per_step(cfg, traffic, batch):
    """(operations, bytes) of the window blocks' forward calls alone."""
    windowed, _ = _kinds(cfg)
    seq, rows = traffic["seq"], traffic["batch"]
    return (rows * windowed * attention_core_flops(
        cfg, seq, cfg["sliding_window_size"]),
        rows * windowed * _call_bytes(cfg, seq))


def flash_window_bwd_per_step(cfg, traffic, batch):
    """(operations, bytes) of the window blocks' backward calls alone
    (``dq`` and ``dkv`` together): dq, dk and dv are five products a pair
    where the forward is two (``Q K^T`` once more, ``dP = dO V^T``, ``dV =
    P^T dO``, ``dK = dS^T Q``, ``dQ = dS K``), two and a half times the
    forward's operations; that each kernel makes the scores for itself is
    recomputation and is not counted."""
    windowed, _ = _kinds(cfg)
    seq, rows = traffic["seq"], traffic["batch"]
    ops = attention_core_flops(cfg, seq, cfg["sliding_window_size"]) * 5 // 2
    return (rows * windowed * ops,
            rows * windowed * _call_bytes(cfg, seq, gradients=True))
