"""Operations and bytes of the ``mhc_mla_moe_lm`` family's training step,
from shapes: the ``mla_moe_lm`` family's count (MLA, the dense and sparse
blocks, the MTP module, the heads; YaRN adds nothing to RoPE, which neither
counts) and the hyper-connections around every sublayer.  A multiply-add
counts as two operations.  What the algorithm needs is counted once:
recomputation is never counted in ``train_step``."""

from . import mla_moe_lm as _mla
from .kda_mla_moe_lm import FLASH_KERNEL, kernel_calls

attention_core_flops = _mla.attention_core_flops


def hc_flops_per_token(cfg):
    """One sublayer's hyper-connection, a token: the norm over the n C
    lanes (square, sum, scale, gain: 4 n C), the projection to n^2 + 2n
    coefficients, the pre-mix (2 n C), the update (H X: 2 n^2 C, h_post y:
    2 n C, the add: n C) and the Sinkhorn's 2 x iters x (n^2 adds and n^2
    divisions) on exp of n^2."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    coeffs = n * n + 2 * n
    return 4 * n * c + 2 * n * c * coeffs + 2 * n * c \
        + 2 * n * n * c + 3 * n * c \
        + n * n + cfg["hc_sinkhorn_iters"] * 2 * 2 * n * n


def hc_bytes_per_token(cfg):
    """What one sublayer's hyper-connection must move a token, float32:
    the streams read by the coefficient path and by the update, the update
    written, the sublayer's input written and its output read."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return 4 * (3 * n * c + 2 * c)


def sublayers(cfg):
    """Sublayers inside a hyper-connection: two a block, the MTP module's
    block among them."""
    return 2 * (cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"])


def forward_flops(cfg, batch, seq):
    return _mla.forward_flops(cfg, batch, seq) \
        + batch * seq * sublayers(cfg) * hc_flops_per_token(cfg)


def train_step(cfg, traffic, batch):
    """Forward and backward of one step: three times the forward."""
    return 3 * forward_flops(cfg, traffic["batch"], traffic["seq"])


def flash_fwd_per_step(cfg, traffic, batch):
    """(operations, bytes) of the flash forward kernel's calls in one step:
    each call a row of an MLA block, as many as ``kernel_calls`` finds in the
    step the trainer compiled (``mla_moe_lm``'s count of two a block
    over-reads by two: PERF.md section 7).  Read Q and K at nope + rope
    lanes, V at ``v_head_dim``, write the output at ``v_head_dim``, once,
    float32."""
    seq, rows = traffic["seq"], traffic["batch"]
    calls = kernel_calls()
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    byts = 4 * seq * cfg["num_attention_heads"] * (2 * qk
                                                   + 2 * cfg["v_head_dim"])
    return calls * attention_core_flops(cfg, seq) * rows, calls * byts * rows
