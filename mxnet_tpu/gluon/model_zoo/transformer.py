"""Transformer model family: BERT (GluonNLP-style) and seq2seq NMT
(Sockeye-style).

Reference parity: the reference framework itself ships no transformer — the
BASELINE configs #3 (BERT-base pretrain, GluonNLP) and #4 (Sockeye
transformer NMT) are downstream repos built on Gluon/Symbol APIs
(SURVEY.md §1 tail).  This module provides the equivalent model family on
our Gluon, written TPU-first:

- one fused QKV projection per attention block (single MXU matmul),
- parameter names chose so `TP_RULES` (megatron-style tensor parallelism)
  applies by regex: `*qkv_weight` column-parallel, `*proj_weight`
  row-parallel, `*ffn1*` column-, `*ffn2*` row-parallel,
- static shapes throughout (mask arrives as a runtime tensor, never a
  Python branch), so one XLA computation per (batch, seq) bucket —
  the BucketingModule discipline of SURVEY.md §5.7.
"""
from __future__ import annotations

import math
from typing import Optional

from ...base import MXNetError
from ..block import HybridBlock
from ..nn import Dense, Dropout, Embedding, HybridSequential, LayerNorm, \
    RMSNorm, SwiGLU

__all__ = ["SlidingWindowSelfAttention", "LongformerEncoderCell",
           "LongformerEncoder",
           "MultiHeadAttention", "PositionwiseFFN", "TransformerEncoderCell",
           "TransformerDecoderCell", "TransformerEncoder",
           "TransformerDecoder", "TransformerNMT", "BERTEncoder",
           "BERTModel", "bert_base", "bert_small", "transformer_nmt_base",
           "CausalLMCell", "CausalLM", "causal_lm_small",
           "MLAttention", "HyperConnection", "MLADecoderCell", "MTPModule",
           "MLAMoELM",
           "GatedDeltaNet", "QKNormAttention", "HybridDecoderCell",
           "OlmoHybridLM", "GroupedQueryAttention", "WindowMoEDecoderCell",
           "WindowMoELM", "TP_RULES"]

#: megatron-style tensor-parallel PartitionSpecs for this family — pass to
#: parallel.ShardingRules(TP_RULES)
TP_RULES = [
    (r".*qkv_weight$", ("tp", None)),
    (r".*qkv_bias$", ("tp",)),
    (r".*kv_weight$", ("tp", None)),
    (r".*kv_bias$", ("tp",)),
    (r".*q_weight$", ("tp", None)),
    (r".*q_bias$", ("tp",)),
    (r".*proj_weight$", (None, "tp")),
    (r".*ffn1_weight$", ("tp", None)),
    (r".*ffn1_bias$", ("tp",)),
    (r".*ffn2_weight$", (None, "tp")),
    (r".*word_embed_weight$", ("tp", None)),
]


def _masked_softmax(F, scores, mask):
    """scores (B*H, Sq, Sk); mask same shape, 1=keep, 0=drop (any dtype)."""
    if mask is not None:
        # additive -1e9 mask (pad-and-mask — the XLA-friendly form)
        scores = scores + (F.cast(mask, dtype="float32") - 1.0) * 1e9
    return F.softmax(scores, axis=-1)


def _flash_eligible(F, mask, valid_len, drop) -> bool:
    # Kernel selection policy (auto by default on TPU):
    #   MXNET_ATTENTION_KERNEL=flash  force the Pallas kernel
    #   MXNET_ATTENTION_KERNEL=xla    force the full-softmax XLA path
    #   unset/auto                    flash on the TPU backend when the
    #                                 mask is expressible, XLA otherwise
    # Eligibility regardless of policy: none-mask always works;
    # explicit ``valid_len`` lengths ride the kernel's per-row
    # masking.  An arbitrary (B*H,Sq,Sk) mask WITHOUT lengths falls
    # back to the XLA path — a 2-D mask cannot be proven to be a
    # prefix mask under trace, and collapsing a non-prefix mask to a
    # length silently corrupts attention (caught in round-4 review).
    # The kernel is differentiable (its custom VJP runs the backward's
    # own Pallas kernels), so training may ride it too — EXCEPT when this
    # block has attention dropout and dropout is live (train_mode/
    # record), since the flash path has no probs tensor to drop.
    from ...base import get_env
    mode = get_env("MXNET_ATTENTION_KERNEL").lower()
    if mode in ("xla", "off", "0"):
        return False
    if mask is not None and valid_len is None:
        return False
    if not hasattr(F, "flash_attention"):
        return False
    if drop is not None:
        from ... import autograd
        if autograd.is_recording() or autograd.is_training():
            return False
    if mode == "flash":
        return True
    # auto: default to flash only where Mosaic actually compiles — on
    # the TPU backend (eager or under whole-graph jit).  Off-TPU the
    # kernel would run in interpret mode, orders of magnitude slower
    # than XLA's fused softmax.
    import jax
    return jax.default_backend() == "tpu"


class MultiHeadAttention(HybridBlock):
    """Scaled dot-product attention with fused QKV.

    Self-attention: call with (x, mask).  Cross-attention: (x, mask, mem)
    — queries from x, keys/values from mem (one q proj + one fused kv).
    """

    def __init__(self, units, num_heads, dropout=0.0, self_attention=True,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by heads "
                             f"{num_heads}")
        self._units = units
        self._heads = num_heads
        self._self = self_attention
        with self.name_scope():
            if self_attention:
                self.qkv = Dense(3 * units, flatten=False, in_units=units,
                                 prefix="qkv_")
            else:
                self.q_proj = Dense(units, flatten=False, in_units=units,
                                    prefix="q_")
                self.kv = Dense(2 * units, flatten=False, in_units=units,
                                prefix="kv_")
            self.proj = Dense(units, flatten=False, in_units=units,
                              prefix="proj_")
            self.drop = Dropout(dropout) if dropout else None

    def _split_heads(self, F, x, batch, seq):
        # (B, S, U) -> (B*H, S, d)
        x = F.reshape(x, shape=(batch, seq, self._heads,
                                self._units // self._heads))
        x = F.transpose(x, axes=(0, 2, 1, 3))
        return F.reshape(x, shape=(batch * self._heads, seq,
                                   self._units // self._heads))

    def _merge_heads(self, F, x, batch, seq):
        x = F.reshape(x, shape=(batch, self._heads, seq,
                                self._units // self._heads))
        x = F.transpose(x, axes=(0, 2, 1, 3))
        return F.reshape(x, shape=(batch, seq, self._units))

    def hybrid_forward(self, F, x, mask=None, mem=None, valid_len=None):
        """``mask``: arbitrary (B*H, Sq, Sk) attention mask (exact XLA
        softmax path).  ``valid_len``: per-sequence key lengths (B,) or
        (B*H,) — the GluonNLP valid_length idiom; authoritative, so the
        flash kernel can honor it even under jit.  Passing both is
        allowed when they express the SAME prefix mask (the XLA path
        uses ``mask``, flash uses ``valid_len``)."""
        b, sq = x.shape[0], x.shape[1]
        h, d = self._heads, self._units // self._heads
        if self._self:
            q = kv = self.qkv(x)
            first, sk = (0, h, 2 * h), sq
        else:
            if mem is None:
                raise MXNetError("cross-attention needs memory input")
            q, kv = self.q_proj(x), self.kv(mem)
            first, sk = (0, 0, h), mem.shape[1]
        scale = 1.0 / math.sqrt(d)
        if self._flash_eligible(F, mask, valid_len):
            # tiled online-softmax Pallas kernel whose custom VJP is two
            # Pallas kernels — differentiable, no (Lq, Lk) score matrix in
            # either direction — that reads each head where the fused
            # projection left it, as a block of the array's lanes: no head
            # is split off or merged back (kernels/flash_attention.py)
            lens = () if valid_len is None else (valid_len,)
            out = F.flash_attention(q, kv, kv, *lens, scale=scale,
                                    num_heads=h, head_dim=d,
                                    first_head=first)
        else:
            import jax
            if self._self:
                q, k, v = F.split(kv, num_outputs=3, axis=-1)
            else:
                k, v = F.split(kv, num_outputs=2, axis=-1)
            q = self._split_heads(F, q, b, sq)
            k = self._split_heads(F, k, b, sk)
            v = self._split_heads(F, v, b, sk)
            with jax.named_scope("attention_xla"):
                scores = F.batch_dot(q, k, transpose_b=True) * scale
                att = _masked_softmax(F, scores, mask)
                if self.drop is not None:
                    att = self.drop(att)
                out = F.batch_dot(att, v)
            out = self._merge_heads(F, out, b, sq)
        return self.proj(out)

    def _flash_eligible(self, F, mask, valid_len) -> bool:
        return _flash_eligible(F, mask, valid_len, self.drop)


class PositionwiseFFN(HybridBlock):
    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.ffn1 = Dense(hidden_size, flatten=False, in_units=units,
                              prefix="ffn1_")
            self.ffn2 = Dense(units, flatten=False, in_units=hidden_size,
                              prefix="ffn2_")
            self.drop = Dropout(dropout) if dropout else None
        self._act = activation

    def hybrid_forward(self, F, x):
        h = self.ffn1(x)
        if self._act == "gelu":
            h = F.LeakyReLU(h, act_type="gelu")   # exact (erf) gelu op
        else:
            h = F.Activation(h, act_type=self._act)
        if self.drop is not None:
            h = self.drop(h)
        return self.ffn2(h)


class TransformerEncoderCell(HybridBlock):
    """Post-LN encoder layer (BERT/Sockeye convention)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 activation="gelu", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.attn = MultiHeadAttention(units, num_heads, dropout,
                                           prefix="attn_")
            self.ln1 = LayerNorm(in_channels=units, prefix="ln1_")
            self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                       activation, prefix="ffn_")
            self.ln2 = LayerNorm(in_channels=units, prefix="ln2_")
            self.drop = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x, mask=None, valid_len=None):
        # positional: Block.__call__ forwards *args only (reference Gluon
        # calling convention); mem slot is None for self-attention
        a = self.attn(x, mask, None, valid_len)
        if self.drop is not None:
            a = self.drop(a)
        x = self.ln1(x + a)
        f = self.ffn(x)
        if self.drop is not None:
            f = self.drop(f)
        return self.ln2(x + f)


class TransformerDecoderCell(HybridBlock):
    """Decoder layer: causal self-attention + cross-attention + FFN."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 activation="relu", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.self_attn = MultiHeadAttention(units, num_heads, dropout,
                                                prefix="selfattn_")
            self.ln1 = LayerNorm(in_channels=units, prefix="ln1_")
            self.cross_attn = MultiHeadAttention(
                units, num_heads, dropout, self_attention=False,
                prefix="crossattn_")
            self.ln2 = LayerNorm(in_channels=units, prefix="ln2_")
            self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                       activation, prefix="ffn_")
            self.ln3 = LayerNorm(in_channels=units, prefix="ln3_")

    def hybrid_forward(self, F, x, causal_mask=None, mem=None,
                       mem_mask=None):
        x = self.ln1(x + self.self_attn(x, causal_mask))
        x = self.ln2(x + self.cross_attn(x, mem_mask, mem))
        return self.ln3(x + self.ffn(x))


def _tie_weight(dense, embed):
    """Share an Embedding's (V, U) weight with a Dense output projection —
    the Dense's own weight parameter is dropped entirely."""
    del dense.params._params[dense.weight.name]
    dense.weight = embed.weight
    dense._reg_params["weight"] = embed.weight


def _positions(F, batch, seq):
    pos = F.arange(seq, dtype="int32")
    return F.broadcast_to(F.reshape(pos, shape=(1, seq)), shape=(batch, seq))


class TransformerEncoder(HybridBlock):
    def __init__(self, num_layers=6, units=512, hidden_size=2048,
                 num_heads=8, max_length=512, dropout=0.0,
                 activation="gelu", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._heads = num_heads
        self._max_len = max_length
        with self.name_scope():
            self.pos_embed = Embedding(max_length, units,
                                       prefix="pos_embed_")
            self.cells = HybridSequential(prefix="layers_")
            with self.cells.name_scope():
                for _ in range(num_layers):
                    self.cells.add(TransformerEncoderCell(
                        units, hidden_size, num_heads, dropout, activation))

    def hybrid_forward(self, F, x, mask=None):
        """x: (B, S, units) embedded input.  mask: (B, S) 1=valid, OR a
        1-D (B,) array of per-sequence valid LENGTHS (the GluonNLP
        valid_length idiom) — the length form is authoritative padding
        information, letting the flash-attention path mask by row length
        instead of falling back to the XLA softmax."""
        b, s = x.shape[0], x.shape[1]
        if s > self._max_len:
            raise ValueError(
                f"sequence length {s} exceeds max_length={self._max_len}")
        x = x + self.pos_embed(_positions(F, b, s))
        att_mask = None
        valid_len = None
        if mask is not None:
            if mask.ndim == 1:                     # (B,) valid lengths
                valid_len = mask
                key_mask = F.broadcast_lesser(
                    F.reshape(F.arange(s, dtype="float32"),
                              shape=(1, s)),
                    F.reshape(F.cast(mask, dtype="float32"),
                              shape=(b, 1)))
            else:                                  # (B, S) 0/1 mask
                key_mask = mask
            # (B,S) -> (B,1,1,S) -> (B*H, Sq, Sk)
            att_mask = F.reshape(key_mask, shape=(b, 1, 1, s))
            att_mask = F.broadcast_to(att_mask,
                                      shape=(b, self._heads, s, s))
            att_mask = F.reshape(att_mask, shape=(-1, s, s))
        for cell in self.cells:
            x = cell(x, att_mask, valid_len)
        return x


class TransformerDecoder(HybridBlock):
    def __init__(self, num_layers=6, units=512, hidden_size=2048,
                 num_heads=8, max_length=512, dropout=0.0,
                 activation="relu", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._heads = num_heads
        self._max_len = max_length
        with self.name_scope():
            self.pos_embed = Embedding(max_length, units,
                                       prefix="pos_embed_")
            self.cells = HybridSequential(prefix="layers_")
            with self.cells.name_scope():
                for _ in range(num_layers):
                    self.cells.add(TransformerDecoderCell(
                        units, hidden_size, num_heads, dropout, activation))

    def hybrid_forward(self, F, x, mem, mem_mask=None):
        b, s = x.shape[0], x.shape[1]
        if s > self._max_len:
            raise ValueError(
                f"sequence length {s} exceeds max_length={self._max_len}")
        sm = mem.shape[1]
        x = x + self.pos_embed(_positions(F, b, s))
        # causal mask (1,S,S) -> (B*H,S,S)
        pos = F.arange(s, dtype="int32")
        causal = F.broadcast_greater_equal(F.reshape(pos, shape=(s, 1)),
                                           F.reshape(pos, shape=(1, s)))
        causal = F.broadcast_to(F.reshape(causal, shape=(1, s, s)),
                                shape=(b * self._heads, s, s))
        mmask = None
        if mem_mask is not None:
            mmask = F.reshape(mem_mask, shape=(b, 1, 1, sm))
            mmask = F.broadcast_to(mmask,
                                   shape=(b, self._heads, s, sm))
            mmask = F.reshape(mmask, shape=(-1, s, sm))
        for cell in self.cells:
            x = cell(x, causal, mem, mmask)
        return x


class TransformerNMT(HybridBlock):
    """Sockeye-style seq2seq transformer (BASELINE config #4): shared
    source/target vocab embedding, encoder-decoder, tied output proj."""

    def __init__(self, vocab_size, num_layers=6, units=512,
                 hidden_size=2048, num_heads=8, max_length=512,
                 dropout=0.0, tie_weights=True, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        with self.name_scope():
            self.word_embed = Embedding(vocab_size, units,
                                        prefix="word_embed_")
            self.encoder = TransformerEncoder(
                num_layers, units, hidden_size, num_heads, max_length,
                dropout, activation="relu", prefix="enc_")
            self.decoder = TransformerDecoder(
                num_layers, units, hidden_size, num_heads, max_length,
                dropout, activation="relu", prefix="dec_")
            self.out_proj = Dense(vocab_size, flatten=False,
                                  in_units=units, use_bias=False,
                                  prefix="out_")
            if tie_weights:
                _tie_weight(self.out_proj, self.word_embed)

    def hybrid_forward(self, F, src, tgt, src_mask=None):
        scale = math.sqrt(self._units)
        mem = self.encoder(self.word_embed(src) * scale, src_mask)
        return self._decode_logits(F, tgt, mem, src_mask)

    # -- inference (the Sockeye translate workflow, config #4) -------------
    def _decode_logits(self, F, tgt, mem, src_mask):
        scale = math.sqrt(self._units)
        dec = self.decoder(self.word_embed(tgt) * scale, mem, src_mask)
        return self.out_proj(dec)

    def translate(self, src, bos: int, eos: int, max_len: int = 50,
                  beam_size: int = 1, alpha: float = 0.6,
                  src_mask=None):
        """Greedy (beam_size=1) or length-normalized beam-search decoding
        (reference workflow: Sockeye's translate CLI over the same
        encoder-decoder; scores use the GNMT length penalty with
        ``alpha``).

        The prefix grows step by step and the decoder re-runs on it —
        per-step jit caches keyed by prefix length keep every step
        compiled (the bucketing discipline of §5.7); the decode-aligned
        flash kernel covers the long-cache regime when enabled.

        Returns (tokens, scores): a list per batch row (EOS stripped)."""
        import numpy as _np

        from ... import ndarray as nd

        scale = math.sqrt(self._units)
        mem = self.encoder(self.word_embed(src) * scale, src_mask)
        b = src.shape[0]
        mem_np_ctx = src.context

        if beam_size <= 1:
            tgt = nd.full((b, 1), bos, ctx=mem_np_ctx)
            finished = _np.zeros((b,), bool)
            logprob = _np.zeros((b,), _np.float64)
            steps = _np.zeros((b,), _np.int64)
            for _ in range(max_len):
                logits = self._decode_logits(nd, tgt, mem, src_mask)
                logp = nd.log_softmax(logits[:, -1, :]).asnumpy()
                nxt = logp.argmax(-1)
                nxt = _np.where(finished, eos, nxt)
                logprob += _np.where(finished, 0.0,
                                     logp[_np.arange(b), nxt])
                steps += (~finished).astype(_np.int64)
                finished |= (nxt == eos)
                tgt = nd.concat(tgt, nd.array(nxt.reshape(b, 1),
                                              ctx=mem_np_ctx), dim=1)
                if finished.all():
                    break
            out = []
            for row in tgt.asnumpy()[:, 1:].astype(int).tolist():
                out.append(row[:row.index(eos)] if eos in row else row)
            # same GNMT length normalization as the beam path, so greedy
            # and beam scores are comparable
            lens = _np.maximum(steps, 1)
            scores = logprob / (((5 + lens) / 6.0) ** alpha)
            return out, [float(s) for s in scores]

        # beam search, one source row at a time (clarity over batching;
        # the per-length jit cache is shared across rows and steps)
        def norm(entry):
            toks, lp, _ = entry
            length = max(len(toks) - 1, 1)
            return lp / (((5 + length) / 6.0) ** alpha)

        results, scores = [], []
        for i in range(b):
            mem_i = mem[i:i + 1]
            mask_i = None if src_mask is None else src_mask[i:i + 1]
            beams = [([bos], 0.0, False)]
            for _ in range(max_len):
                if all(f for _, _, f in beams):
                    break
                cand = []
                for toks, lp, fin in beams:
                    if fin:
                        cand.append((toks, lp, True))
                        continue
                    tgt = nd.array(_np.asarray([toks]), ctx=mem_np_ctx)
                    logits = self._decode_logits(nd, tgt, mem_i, mask_i)
                    logp = nd.log_softmax(logits[0, -1, :]).asnumpy()
                    top = _np.argsort(logp)[-beam_size:]
                    for t in top:
                        cand.append((toks + [int(t)], lp + float(logp[t]),
                                     int(t) == eos))
                cand.sort(key=norm, reverse=True)
                beams = cand[:beam_size]
            best, best_lp, _ = max(beams, key=norm)
            row = best[1:]
            results.append(row[:row.index(eos)] if eos in row else row)
            scores.append(norm((best, best_lp, True)))
        return results, scores


class BERTEncoder(TransformerEncoder):
    """BERT uses the (gelu, post-LN) encoder as-is."""


class BERTModel(HybridBlock):
    """BERT-base-style model (BASELINE config #3): token+segment+position
    embeddings -> encoder -> (MLM decoder over all positions, NSP head
    over [CLS])."""

    def __init__(self, vocab_size=30522, num_layers=12, units=768,
                 hidden_size=3072, num_heads=12, max_length=512,
                 type_vocab_size=2, dropout=0.1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        with self.name_scope():
            self.word_embed = Embedding(vocab_size, units,
                                        prefix="word_embed_")
            self.token_type_embed = Embedding(type_vocab_size, units,
                                              prefix="type_embed_")
            self.embed_ln = LayerNorm(in_channels=units, prefix="embed_ln_")
            self.embed_drop = Dropout(dropout) if dropout else None
            self.encoder = BERTEncoder(
                num_layers, units, hidden_size, num_heads, max_length,
                dropout, activation="gelu", prefix="enc_")
            self.pooler = Dense(units, activation="tanh", flatten=False,
                                in_units=units, prefix="pooler_")
            # MLM: transform + decoder tied to the word embedding (BERT
            # convention — decoder keeps its own bias)
            self.mlm_dense = Dense(units, flatten=False, in_units=units,
                                   prefix="mlm_dense_")
            self.mlm_ln = LayerNorm(in_channels=units, prefix="mlm_ln_")
            self.mlm_decoder = Dense(vocab_size, flatten=False,
                                     in_units=units, prefix="mlm_out_")
            _tie_weight(self.mlm_decoder, self.word_embed)
            self.nsp = Dense(2, flatten=False, in_units=units,
                             prefix="nsp_")

    def hybrid_forward(self, F, tokens, token_types, valid_mask=None):
        x = self.word_embed(tokens) + self.token_type_embed(token_types)
        x = self.embed_ln(x)
        if self.embed_drop is not None:
            x = self.embed_drop(x)
        seq = self.encoder(x, valid_mask)                 # (B, S, U)
        h = F.LeakyReLU(self.mlm_dense(seq), act_type="gelu")
        mlm = self.mlm_decoder(self.mlm_ln(h))            # (B, S, V)
        cls = F.squeeze(F.slice_axis(seq, axis=1, begin=0, end=1), axis=1)
        nsp = self.nsp(self.pooler(cls))                  # (B, 2)
        return mlm, nsp


def bert_base(vocab_size=30522, **kwargs):
    return BERTModel(vocab_size=vocab_size, num_layers=12, units=768,
                     hidden_size=3072, num_heads=12, **kwargs)


def bert_small(vocab_size=1000, **kwargs):
    """Tiny config for tests/dryruns."""
    kwargs.setdefault("max_length", 128)
    return BERTModel(vocab_size=vocab_size, num_layers=2, units=64,
                     hidden_size=128, num_heads=4, **kwargs)


def transformer_nmt_base(vocab_size=32000, **kwargs):
    return TransformerNMT(vocab_size, num_layers=6, units=512,
                          hidden_size=2048, num_heads=8, **kwargs)


class SlidingWindowSelfAttention(HybridBlock):
    """Longformer-style banded self-attention over the sliding-window op
    trio (reference family: src/operator/contrib/transformer.cc
    _sldwin_atten_*).

    Memory is O(L·W) per head instead of O(L²): scores, mask, and
    context all live in the (B, L, H, 2w+1) band, so sequence length
    scales linearly — the single-chip long-context complement to the
    ring/sequence-parallel path in ``parallel/ring.py``.  Layout
    follows the reference ops: (B, L, H, D) head tensors, per-head
    dilation, symmetric window of one-sided width ``w``."""

    def __init__(self, units, num_heads, w, dilation=None, dropout=0.0,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by heads "
                             f"{num_heads}")
        self._units = units
        self._heads = num_heads
        self._w = int(w)
        self._dilation = tuple(dilation) if dilation is not None else \
            (1,) * num_heads
        if len(self._dilation) != num_heads:
            raise MXNetError("dilation needs one entry per head")
        with self.name_scope():
            self.qkv = Dense(3 * units, flatten=False, in_units=units,
                             prefix="qkv_")
            self.proj = Dense(units, flatten=False, in_units=units,
                              prefix="proj_")
            self.drop = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x, valid_len=None):
        b, l = x.shape[0], x.shape[1]
        d = self._units // self._heads
        qkv = self.qkv(x)
        q, k, v = F.split(qkv, num_outputs=3, axis=-1)
        # (B, L, H, D) — the sldwin op layout
        q = F.reshape(q, shape=(b, l, self._heads, d))
        k = F.reshape(k, shape=(b, l, self._heads, d))
        v = F.reshape(v, shape=(b, l, self._heads, d))
        scale = 1.0 / math.sqrt(d)
        if not hasattr(F, "array"):
            raise MXNetError(
                "SlidingWindowSelfAttention supports the imperative/"
                "hybridize path; compose the _sldwin_atten_* ops "
                "directly for hand-built Symbol graphs")
        import numpy as _np
        dil = F.array(_np.asarray(self._dilation, _np.int32))
        if valid_len is None:
            valid_len = F.full((b,), l)
        s = F._sldwin_atten_score(q, k, dil, w=self._w,
                                  symmetric=True) * scale
        m = F._sldwin_atten_mask_like(s, dil, valid_len, w=self._w,
                                      symmetric=True)
        att = F.softmax(s + (1.0 - m) * -1e9, axis=-1) * m
        if self.drop is not None:
            att = self.drop(att)
        ctx = F._sldwin_atten_context(att, v, dil, w=self._w,
                                      symmetric=True)
        return self.proj(F.reshape(ctx, shape=(b, l, self._units)))


class LongformerEncoderCell(HybridBlock):
    """Post-LN encoder layer with banded self-attention."""

    def __init__(self, units, hidden_size, num_heads, w, dilation=None,
                 dropout=0.0, activation="gelu", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.attn = SlidingWindowSelfAttention(
                units, num_heads, w, dilation, dropout, prefix="attn_")
            self.ln1 = LayerNorm(in_channels=units, prefix="ln1_")
            self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                       activation, prefix="ffn_")
            self.ln2 = LayerNorm(in_channels=units, prefix="ln2_")
            self.drop = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x, valid_len=None):
        a = self.attn(x, valid_len)
        if self.drop is not None:
            a = self.drop(a)
        x = self.ln1(x + a)
        f = self.ffn(x)
        if self.drop is not None:
            f = self.drop(f)
        return self.ln2(x + f)


class LongformerEncoder(HybridBlock):
    """Token+position embedding over N banded encoder layers — the
    long-sequence encoder family (Longformer): O(L·w) attention admits
    sequence lengths the dense BERT encoder cannot hold."""

    def __init__(self, vocab_size, num_layers=2, units=64,
                 hidden_size=128, num_heads=4, w=32, dilation=None,
                 max_length=4096, dropout=0.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        with self.name_scope():
            self.tok = Embedding(vocab_size, units, prefix="tok_")
            self.pos = Embedding(max_length, units, prefix="pos_")
            self.layers = HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for _ in range(num_layers):
                    self.layers.add(LongformerEncoderCell(
                        units, hidden_size, num_heads, w, dilation,
                        dropout))
            self.ln = LayerNorm(in_channels=units, prefix="ln_")
        # same cell objects, public iteration order: valid_len must
        # thread through each cell, which Sequential's own __call__
        # cannot do
        self._cells = [c for c in self.layers]

    def hybrid_forward(self, F, tokens, valid_len=None):
        b, l = tokens.shape[0], tokens.shape[1]
        import numpy as _np
        pos_ids = F.array(_np.arange(l, dtype=_np.int64))
        h = self.tok(tokens) + F.reshape(
            self.pos(pos_ids), shape=(1, l, self._units))
        h = self.ln(h)
        for cell in self._cells:
            h = cell(h, valid_len)
        return h


class CausalLMCell(HybridBlock):
    """Pre-factored decoder-only layer: the generation scheduler's
    prefill/decode graphs reach its children (``qkv``/``proj``/``ln1``/
    ``ffn``/``ln2``) directly, so the cell is both a standard post-LN
    causal layer (``hybrid_forward``) and the parameter container for
    :class:`CausalLM`'s paged-attention entries."""

    def __init__(self, units, hidden_size, num_heads, activation="gelu",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by heads "
                             f"{num_heads}")
        self._units = units
        self._heads = num_heads
        with self.name_scope():
            self.qkv = Dense(3 * units, flatten=False, in_units=units,
                             prefix="qkv_")
            self.proj = Dense(units, flatten=False, in_units=units,
                              prefix="proj_")
            self.ln1 = LayerNorm(in_channels=units, prefix="ln1_")
            self.ffn = PositionwiseFFN(units, hidden_size, 0.0,
                                       activation, prefix="ffn_")
            self.ln2 = LayerNorm(in_channels=units, prefix="ln2_")

    def attend(self, F, x, k, v, mask, batch, sq, sk):
        """Post-LN residual layer body around an explicit K/V set —
        shared by the full pass (K/V = the pass's own projections) and
        the decode step (K/V gathered from the block pool)."""
        h = self._heads
        d = self._units // h
        q = F.split(self.qkv(x), num_outputs=3, axis=-1)[0]
        q = F.reshape(F.transpose(
            F.reshape(q, shape=(batch, sq, h, d)),
            axes=(0, 2, 1, 3)), shape=(batch * h, sq, d))
        kh = F.reshape(F.transpose(
            F.reshape(k, shape=(batch, sk, h, d)),
            axes=(0, 2, 1, 3)), shape=(batch * h, sk, d))
        vh = F.reshape(F.transpose(
            F.reshape(v, shape=(batch, sk, h, d)),
            axes=(0, 2, 1, 3)), shape=(batch * h, sk, d))
        scale = 1.0 / math.sqrt(d)
        att = _masked_softmax(F, F.batch_dot(q, kh, transpose_b=True)
                              * scale, mask)
        out = F.batch_dot(att, vh)                 # (B*H, Sq, d)
        out = F.reshape(F.transpose(
            F.reshape(out, shape=(batch, h, sq, d)),
            axes=(0, 2, 1, 3)), shape=(batch, sq, self._units))
        x = self.ln1(x + self.proj(out))
        return self.ln2(x + self.ffn(x))

    def hybrid_forward(self, F, x, mask=None):
        b, s = x.shape[0], x.shape[1]
        kv = F.split(self.qkv(x), num_outputs=3, axis=-1)
        return self.attend(F, x, kv[1], kv[2], mask, b, s, s)


class CausalLM(HybridBlock):
    """Decoder-only LM with a paged-KV generation contract.

    Three compiled entries share one parameter set:

    - ``hybrid_forward(tokens)`` — full causal pass, (B, S) -> (B, S, V)
      logits (training / eval / the whole-sequence serving baseline);
    - ``hybrid_prefill(tokens, seq_len, table, pool)`` — ONE prompt
      (batch 1) padded to a length bucket: causal attention within the
      prompt, every position's K/V scattered into the request's KV
      blocks (``table`` maps position//block -> pool block id), returns
      (last-real-position logits (1, V), updated pool);
    - ``hybrid_decode(tokens, positions, tables, pool)`` — one token
      per running slot: scatter the step's K/V at each slot's current
      position, gather each slot's whole block list back, attend under
      a per-slot length mask, return ((slots, V) logits, updated pool).

    The pool is a single ``(2*num_layers, n_blocks, block, units)``
    array (K rows even, V rows odd).  Block 0 is scratch by convention
    (``serving.kv_cache``): empty slots and table-tail entries point at
    it, and the additive -1e9 mask underflows their attention weight to
    an exact float32 zero — so each slot's output is bitwise-independent
    of every other slot and of pool garbage, which is what makes
    continuous-batched greedy decode bitwise-equal to decoding alone.
    """

    def __init__(self, vocab_size=257, num_layers=2, units=64,
                 hidden_size=128, num_heads=4, max_length=256,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._heads = num_heads
        self._layers = num_layers
        self._max_len = max_length
        with self.name_scope():
            self.word_embed = Embedding(vocab_size, units,
                                        prefix="word_embed_")
            self.pos_embed = Embedding(max_length, units,
                                       prefix="pos_embed_")
            self.layers = HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for _ in range(num_layers):
                    self.layers.add(CausalLMCell(units, hidden_size,
                                                 num_heads))
            self.out_proj = Dense(vocab_size, flatten=False,
                                  in_units=units, use_bias=False,
                                  prefix="out_")
            _tie_weight(self.out_proj, self.word_embed)
        # public iteration order: prefill/decode thread extra state the
        # Sequential __call__ cannot (the LongformerEncoder idiom)
        self._cells = [c for c in self.layers]

    # -- shared pieces ------------------------------------------------
    def init_kv_pool(self, n_blocks, block_size):
        """Zero-initialized pool with this model's layout — what the
        generation scheduler allocates once per server."""
        import numpy as _np
        return _np.zeros((2 * self._layers, int(n_blocks),
                          int(block_size), self._units), _np.float32)

    def _causal(self, F, b, s):
        pos = F.arange(s, dtype="int32")
        causal = F.broadcast_greater_equal(F.reshape(pos, shape=(s, 1)),
                                           F.reshape(pos, shape=(1, s)))
        return F.broadcast_to(F.reshape(causal, shape=(1, s, s)),
                              shape=(b * self._heads, s, s))

    def _block_coords(self, F, positions):
        """position -> (block index within the table, offset in block);
        integer //, % via sub-and-divide (exact for pool-sized ints)."""
        rem = positions % self._bs
        bidx = F.cast((positions - rem) / float(self._bs), dtype="int32")
        return bidx, rem

    def _scatter_kv(self, F, pool, layer, blocks, offsets, k, v, n):
        """Functional write of one layer's K and V rows at
        (block, offset) per entry — positions past a request's
        allocation land in scratch block 0 (masked, finite, ignored)."""
        lk = F.full((n,), 2 * layer, dtype="int32")
        lv = F.full((n,), 2 * layer + 1, dtype="int32")
        pool = F._scatter_set_nd(
            pool, k, F.stack(lk, blocks, offsets, axis=0, num_args=3))
        return F._scatter_set_nd(
            pool, v, F.stack(lv, blocks, offsets, axis=0, num_args=3))

    # -- full pass (training / whole-sequence baseline) ---------------
    def hybrid_forward(self, F, tokens):
        b, s = tokens.shape[0], tokens.shape[1]
        x = self.word_embed(tokens) + self.pos_embed(_positions(F, b, s))
        mask = self._causal(F, b, s)
        for cell in self._cells:
            x = cell(x, mask)
        return self.out_proj(x)

    # -- generation entries (serving.ModelServer.serve_generation) ----
    @property
    def _bs(self):
        return self._pool_block

    def hybrid_prefill(self, F, tokens, seq_len, table, pool):
        """tokens (1, L) int32 padded to a length bucket; seq_len (1,)
        int32; table (1, W) int32 block ids (W = ceil(L/block), tail =
        scratch); pool as in :meth:`init_kv_pool`.  Returns
        ((1, V) logits at the last real position, updated pool)."""
        l = tokens.shape[1]
        bs = pool.shape[2]
        self._pool_block = bs
        x = self.word_embed(tokens) + self.pos_embed(_positions(F, 1, l))
        mask = self._causal(F, 1, l)
        pos = F.arange(l, dtype="int32")
        bidx, rem = self._block_coords(F, pos)
        blocks = F.take(F.reshape(table, shape=(-1,)), bidx, axis=0)
        for i, cell in enumerate(self._cells):
            kv = F.split(cell.qkv(x), num_outputs=3, axis=-1)
            pool = self._scatter_kv(
                F, pool, i, blocks, rem,
                F.reshape(kv[1], shape=(l, self._units)),
                F.reshape(kv[2], shape=(l, self._units)), l)
            x = cell.attend(F, x, kv[1], kv[2], mask, 1, l, l)
        last = F.take(F.reshape(x, shape=(l, self._units)),
                      seq_len - 1, axis=0)              # (1, U)
        return self.out_proj(last), pool

    def hybrid_decode(self, F, tokens, positions, tables, pool):
        """One decode step for the whole running batch: tokens (slots,)
        int32; positions (slots,) int32 (each token's position = the
        sequence length before it); tables (slots, W) int32; pool as in
        :meth:`init_kv_pool`.  Returns ((slots, V) logits, updated
        pool).  Every op is row-independent, so a slot's logits depend
        only on its own token/position/table — the bitwise-equality
        contract continuous batching is tested against."""
        slots = tokens.shape[0]
        w = tables.shape[1]
        bs = pool.shape[2]
        self._pool_block = bs
        s_keys = w * bs
        x = self.word_embed(tokens) + self.pos_embed(positions)
        bidx, rem = self._block_coords(F, positions)
        blocks = F.pick(tables, bidx, axis=-1)          # (slots,)
        # per-slot prefix mask over the gathered key window: key j
        # visible iff j <= position (the new token sees itself)
        keep = F.broadcast_lesser_equal(
            F.reshape(F.arange(s_keys, dtype="int32"), shape=(1, s_keys)),
            F.reshape(positions, shape=(slots, 1)))     # (slots, S)
        mask = F.reshape(F.broadcast_to(
            F.reshape(keep, shape=(slots, 1, 1, s_keys)),
            shape=(slots, self._heads, 1, s_keys)),
            shape=(slots * self._heads, 1, s_keys))
        for i, cell in enumerate(self._cells):
            kv = F.split(cell.qkv(x), num_outputs=3, axis=-1)
            pool = self._scatter_kv(F, pool, i, blocks, rem,
                                    kv[1], kv[2], slots)
            kc = F.reshape(F.take(pool[2 * i], tables, axis=0),
                           shape=(slots, s_keys, self._units))
            vc = F.reshape(F.take(pool[2 * i + 1], tables, axis=0),
                           shape=(slots, s_keys, self._units))
            x3 = F.reshape(x, shape=(slots, 1, self._units))
            x3 = cell.attend(F, x3, kc, vc, mask, slots, 1, s_keys)
            x = F.reshape(x3, shape=(slots, self._units))
        return self.out_proj(x), pool


def causal_lm_small(vocab_size=257, **kwargs):
    """Tiny decoder-only LM for tests — the generation-serving
    counterpart of ``bert_small``."""
    kwargs.setdefault("max_length", 256)
    return CausalLM(vocab_size=vocab_size, num_layers=2, units=64,
                    hidden_size=128, num_heads=4, **kwargs)


# ---------------------------------------------------------------------------
# Latent attention (MLA), sparse experts and multi-token prediction: the
# pre-norm causal decoder of the DeepSeek-V3 / GLM-4.x line
# ---------------------------------------------------------------------------

def _causal_attention(F, q, k, v, scale, heads=None, kernel_serves=True,
                      kv_heads=None, window=None):
    """Causal softmax attention for training: the flash kernel where the
    selector takes it and its shapes serve, else the full softmax through
    XLA.  The operands are heads-first, (B*H, S, D), or with ``heads`` given
    tokens-major, (B, S, heads * D) as a projection leaves them: the kernel
    then reads a head as a block of the lanes, and only the XLA path
    splits the heads off.  ``kv_heads`` (tokens-major): k and v hold that
    many heads, each read by ``heads / kv_heads`` query heads in a row;
    ``window``: a query weighs its own key and the ``window - 1`` before
    it.  Both paths take both, the XLA path by a mask and a reshape to
    (B * kv_heads, group * S, D), so neither repeats a key head."""
    import jax
    if kernel_serves and _flash_eligible(F, None, None, None):
        return F.flash_attention(q, k, v, causal=True, scale=scale,
                                 num_heads=heads, num_kv_heads=kv_heads,
                                 window=window)
    b, s = q.shape[0], q.shape[1]
    n = heads if kv_heads is None else kv_heads
    group = 1 if heads is None else heads // n

    def heads_first(t, per_key_head):
        # (b, s, n * g * d) -> (b * n, g * s, d): the g query heads of a
        # group one after the other down the rows of their key head
        if heads is None:
            return t
        t = F.reshape(t, shape=(b, s, n, per_key_head, -1))
        return F.reshape(F.transpose(t, axes=(0, 2, 3, 1, 4)),
                         shape=(b * n, per_key_head * s, -1))
    with jax.named_scope("attention_xla"):
        q, k, v = heads_first(q, group), heads_first(k, 1), heads_first(v, 1)
        at, key = F.arange(s).reshape((s, 1)), F.arange(s).reshape((1, s))
        keep = key <= at
        if window is not None:
            keep = keep * (at - key < window)
        if group > 1:
            keep = F.tile(keep, reps=(group, 1))
        keep = F.reshape(keep, shape=(1, group * s, s))
        scores = F.batch_dot(q, k, transpose_b=True) * scale
        out = F.batch_dot(_masked_softmax(
            F, scores, F.broadcast_to(keep, shape=scores.shape)), v)
    if heads is None:
        return out
    return F.reshape(F.transpose(
        F.reshape(out, shape=(b, n, group, s, -1)), axes=(0, 3, 1, 2, 4)),
        shape=(b, s, -1))


class MLAttention(HybridBlock):
    """Multi-head latent attention, causal, for training (no cache): the
    queries go through a ``q_lora_rank`` bottleneck (None: one projection,
    ``q``), keys and values are expanded from one ``kv_lora_rank`` latent
    per token, and one key of ``qk_rope_head_dim`` lanes is shared by every
    head, rotated by position where ``rope`` (with ``rope`` False those
    lanes carry no position: Kimi Linear's ``mla_use_nope``).  A head's
    query and key are ``[nope | rope]``, its value ``v_head_dim`` wide (the
    flash kernels take it at its own width).  ``rope_scaling``: None, or
    the configuration's YaRN ``rope_scaling``, which sets the rotary
    frequencies (the ``rope`` op's ``scaling``) and multiplies the softmax
    scale by ``yarn_softmax_factor``."""

    def __init__(self, units, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=10000.0, epsilon=1e-5, rope=True,
                 rope_scaling=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        from ...ndarray.ops_nn import yarn_softmax_factor
        self._heads, self._nope, self._rope, self._vd = \
            num_heads, qk_nope_head_dim, qk_rope_head_dim, v_head_dim
        self._kvr, self._theta, self._rotate = kv_lora_rank, rope_theta, rope
        self._lora_q = q_lora_rank is not None
        # the op's keyword as hashable pairs, so that its function is cached
        self._rope_kw = {} if rope_scaling is None else {
            "scaling": tuple(sorted(dict(rope_scaling).items()))}
        self._softmax_factor = 1.0 if rope_scaling is None else \
            yarn_softmax_factor(dict(rope_scaling))
        qk = qk_nope_head_dim + qk_rope_head_dim

        def lin(out, inp, name):
            return Dense(out, use_bias=False, flatten=False, in_units=inp,
                         prefix=name)
        with self.name_scope():
            if q_lora_rank is None:
                self.q = lin(num_heads * qk, units, "q_")
            else:
                self.q_down = lin(q_lora_rank, units, "q_down_")
                self.q_norm = RMSNorm(epsilon=epsilon,
                                      in_channels=q_lora_rank,
                                      prefix="q_norm_")
                self.q_up = lin(num_heads * qk, q_lora_rank, "q_up_")
            self.kv_down = lin(kv_lora_rank + qk_rope_head_dim, units,
                               "kv_down_")
            self.kv_norm = RMSNorm(epsilon=epsilon, in_channels=kv_lora_rank,
                                   prefix="kv_norm_")
            self.kv_up = lin(num_heads * (qk_nope_head_dim + v_head_dim),
                             kv_lora_rank, "kv_up_")
            self.proj = lin(units, num_heads * v_head_dim, "proj_")

    def hybrid_forward(self, F, x):
        import jax
        b, s = x.shape[0], x.shape[1]
        h, nope, rd, vd = self._heads, self._nope, self._rope, self._vd
        q = F.reshape(self.q_up(self.q_norm(self.q_down(x))) if self._lora_q
                      else self.q(x),
                      shape=(b, s, h, nope + rd))
        ckv = self.kv_down(x)
        kv = F.reshape(
            self.kv_up(self.kv_norm(F.slice_axis(ckv, axis=-1, begin=0,
                                                 end=self._kvr))),
            shape=(b, s, h, nope + vd))

        def shared_key(k_rope):
            return F.concat(
                F.slice_axis(kv, axis=-1, begin=0, end=nope),
                F.broadcast_axis(F.expand_dims(k_rope, axis=2), axis=2,
                                 size=h), dim=-1)
        if self._rotate:
            with jax.named_scope("rope"):
                q = F.rope(q, base=self._theta, rotary_dim=rd, seq_axis=1,
                           **self._rope_kw)
                k = shared_key(F.rope(
                    F.slice_axis(ckv, axis=-1, begin=self._kvr, end=None),
                    base=self._theta, seq_axis=1, **self._rope_kw))
        else:
            k = shared_key(F.slice_axis(ckv, axis=-1, begin=self._kvr,
                                        end=None))
        v = F.slice_axis(kv, axis=-1, begin=nope, end=None)

        # heads-first, not the lanes of (b, s, h * 256): q, k and v are made
        # a head at a time (rotary lanes, one shared rotary key, v cut out
        # of a head's 448), so XLA writes them out once either way ((b, s,
        # 20, 256) and (b, s, 5120) are tiled differently on the chip), and
        # written heads-first a K-major block is one contiguous slab: the
        # backward's kernels, which fetch each block many times, read
        # 2.5-8.6 % slower from 1 KB rows (PERF.md section 6, PR 35)
        def heads_first(t, width):
            return F.reshape(F.transpose(t, axes=(0, 2, 1, 3)),
                             shape=(b * h, s, width))
        q, k, v = heads_first(q, nope + rd), heads_first(k, nope + rd), \
            heads_first(v, vd)
        out = _causal_attention(F, q, k, v,
                                self._softmax_factor / math.sqrt(nope + rd),
                                kernel_serves=vd <= nope + rd)
        out = F.transpose(F.reshape(out, shape=(b, h, s, vd)),
                          axes=(0, 2, 1, 3))
        return self.proj(F.reshape(out, shape=(b, s, h * vd)))


class HyperConnection(HybridBlock):
    """One sublayer's manifold-constrained hyper-connection
    (``kernels/hyper_connection.py`` has the equations) over ``streams``
    residual streams of ``units``: ``gamma`` the coefficient norm's gain,
    ``phi`` (streams^2 + 2 streams, streams x units), ``bias``, ``alpha``
    (pre, post, res).  Called on the state X (B, S, streams x units), the
    streams side by side, it returns ``(x_in, coef, X)`` under the scopes
    ``hc/pre``: the sublayer's input (B, S, units), the coefficients in
    float32 and X itself; ``F.hyper_connection_mix(X, y, coef)`` with that
    X and the sublayer's output ``y`` is the new state (scope ``hc/mix``),
    and needs no parameter.  The mix takes the X that ``pre`` hands back, so
    that the backward adds both cotangents of X in one place."""

    def __init__(self, units, streams, sinkhorn_iters=20, eps=1e-6,
                 clamp=(-30.0, 30.0), prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        from ...initializer import Constant, Normal
        self._kw = dict(iters=sinkhorn_iters, eps=eps,
                        clamp=tuple(float(c) for c in clamp))
        width, outs = streams * units, streams * (streams + 2)
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(width,),
                                         init="ones")
            self.phi = self.params.get("phi", shape=(outs, width),
                                       init=Normal(0.02))
            self.bias = self.params.get("bias", shape=(outs,), init="zeros")
            self.alpha = self.params.get("alpha", shape=(3,),
                                         init=Constant(0.01))

    def hybrid_forward(self, F, x, gamma, phi, bias, alpha):
        return F.hyper_connection_pre(x, phi, bias, alpha, gamma, **self._kw)


class MLADecoderCell(HybridBlock):
    """Pre-norm block: ``x + Mixer(RMSNorm(x))``, then ``x +
    FFN(RMSNorm(x))`` with ``ffn`` a dense SwiGLU or a sparse-expert layer;
    the mixer, ``attention()``, is the block's attribute of the name
    ``mixer`` (``mla``: latent attention; ``gdn``: the channel-gated delta
    rule).  With ``hc_mult`` streams (mHC) the block's input and output are
    (B, S, hc_mult x units), the streams side by side, and each sublayer,
    norm and mixer or norm and FFN, sits inside a ``HyperConnection``
    (``attn_hc``, ``ffn_hc``) in place of the plain residual add."""

    def __init__(self, units, attention, ffn, epsilon=1e-5, prefix=None,
                 params=None, mixer="mla", hc_mult=None, hc_sinkhorn_iters=20,
                 hc_eps=1e-6, hc_clamp=(-30.0, 30.0)):
        super().__init__(prefix=prefix, params=params)
        self._mixer = mixer
        self._hc = hc_mult is not None
        with self.name_scope():
            self.attn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                     prefix="attn_norm_")
            setattr(self, mixer, attention(prefix=mixer + "_"))
            self.ffn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                    prefix="ffn_norm_")
            self.ffn = ffn()
            if self._hc:
                hc = dict(streams=hc_mult, sinkhorn_iters=hc_sinkhorn_iters,
                          eps=hc_eps, clamp=hc_clamp)
                self.attn_hc = HyperConnection(units, prefix="attn_hc_", **hc)
                self.ffn_hc = HyperConnection(units, prefix="ffn_hc_", **hc)

    def hybrid_forward(self, F, x):
        if not self._hc:
            x = x + getattr(self, self._mixer)(self.attn_norm(x))
            return x + self.ffn(self.ffn_norm(x))
        for hc, sublayer in (
                (self.attn_hc, lambda h: getattr(self, self._mixer)(
                    self.attn_norm(h))),
                (self.ffn_hc, lambda h: self.ffn(self.ffn_norm(h)))):
            x_in, coef, x = hc(x)
            x = F.hyper_connection_mix(x, sublayer(x_in), coef)
        return x


class MTPModule(HybridBlock):
    """One multi-token-prediction depth: the main stack's last hidden state
    (before its final norm) and the embedding of the NEXT token, each
    normed, concatenated ``[embedding ; hidden]`` and projected back to the
    width; one decoder cell; a final norm of its own.  The embedding and
    the head are the model's.  With ``hc_mult`` streams the projection is
    spread into streams of its own, the cell runs on them, and they are
    summed before the final norm (the hidden state is the main stack's
    streams summed)."""

    def __init__(self, units, cell, epsilon=1e-5, prefix=None, params=None,
                 hc_mult=None):
        super().__init__(prefix=prefix, params=params)
        self._streams = hc_mult
        with self.name_scope():
            self.enorm = RMSNorm(epsilon=epsilon, in_channels=units,
                                 prefix="enorm_")
            self.hnorm = RMSNorm(epsilon=epsilon, in_channels=units,
                                 prefix="hnorm_")
            self.eh_proj = Dense(units, use_bias=False, flatten=False,
                                 in_units=2 * units, prefix="eh_proj_")
            self.cell = cell(prefix="cell_")
            self.final_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                      prefix="final_norm_")

    def hybrid_forward(self, F, hidden, next_embed):
        h = self.eh_proj(F.concat(self.enorm(next_embed), self.hnorm(hidden),
                                  dim=-1))
        if self._streams is None:
            return self.final_norm(self.cell(h))
        return self.final_norm(_merge(F, self.cell(_spread(F, h,
                                                           self._streams)),
                                      self._streams))


def _spread(F, x, streams):
    """(B, S, C) -> (B, S, streams x C): the same values in every stream,
    the streams side by side."""
    return F.tile(x, reps=(1, 1, streams))


def _merge(F, x, streams):
    """(B, S, streams x C) -> (B, S, C): the streams summed."""
    return F.sum(F.reshape(x, shape=(0, 0, streams, -1)), axis=2)


class MLAMoELM(HybridBlock):
    """Causal language model of MLA decoder cells: ``first_dense`` leading
    cells with a dense SwiGLU of ``hidden_size``, the rest with a
    ``parallel.moe.SparseMoE`` that holds ``experts_held`` of the
    ``num_experts`` routed experts (one chip's share) beside a shared
    expert; an untied head; ``num_mtp`` (0 or 1) MTP module.

    ``layer_types`` (one a layer; None: all ``"full_attention"``) gives
    each cell's mixer: ``"full_attention"`` is the MLA block,
    ``"linear_attention"`` a ``KimiDeltaAttention`` of ``linear_num_heads``
    heads of ``linear_head_dim`` lanes with a convolution of
    ``linear_conv_size`` taps (Kimi Linear).  ``mla_rope`` False: the MLA
    blocks take no rotary position; ``q_lora_rank`` None: one query
    projection; ``rope_scaling``: YaRN's, for the MLA blocks.

    ``hc_mult`` (None: the plain residual path) streams of manifold-
    constrained hyper-connections (``HyperConnection``, with
    ``hc_sinkhorn_iters``, ``hc_eps`` and ``hc_clamp``) around every
    sublayer: the embedding goes into every stream as it is, the streams
    are summed before the final norm, and the MTP module takes that sum and
    runs its cell on streams of its own.

    ``net(tokens)`` returns the logits (B, S, vocab), and with an MTP
    module ``(logits, mtp_logits)``: ``mtp_logits[:, i]`` scores token
    ``i + 2`` (the module's input at position i is the embedding of token
    ``i + 1``; the last position's input wraps round and has no target).
    ``remat_blocks`` lists the blocks a trainer rematerialises.
    """

    def __init__(self, vocab_size, units, num_layers, num_heads, q_lora_rank,
                 kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 hidden_size, moe_hidden_size, num_experts, top_k,
                 experts_held=None, num_shared_experts=1, routed_scale=1.0,
                 norm_topk=True, first_dense=1, num_mtp=1, rope_theta=10000.0,
                 epsilon=1e-5, layer_types=None, linear_num_heads=None,
                 linear_head_dim=None, linear_conv_size=4, mla_rope=True,
                 rope_scaling=None, hc_mult=None, hc_sinkhorn_iters=20,
                 hc_eps=1e-6, hc_clamp=(-30.0, 30.0), prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        import functools
        from ...parallel.moe import SparseMoE
        if num_mtp not in (0, 1):
            raise MXNetError(f"num_mtp must be 0 or 1, got {num_mtp!r}")
        kinds = ["full_attention"] * num_layers if layer_types is None \
            else list(layer_types)
        if len(kinds) != num_layers:
            raise MXNetError(f"{len(kinds)} layer_types for {num_layers} "
                             "layers")
        attention = functools.partial(
            MLAttention, units, num_heads, q_lora_rank, kv_lora_rank,
            qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
            rope_theta=rope_theta, epsilon=epsilon, rope=mla_rope,
            rope_scaling=rope_scaling)
        mixers = {"full_attention": (attention, "mla")}
        if "linear_attention" in kinds:
            mixers["linear_attention"] = (functools.partial(
                KimiDeltaAttention, units, linear_num_heads, linear_head_dim,
                conv_size=linear_conv_size, epsilon=epsilon), "gdn")
        for i, kind in enumerate(kinds):
            if kind not in mixers:
                raise MXNetError(f"layer_types[{i}] = {kind!r} is neither "
                                 "'full_attention' nor 'linear_attention'")
        dense = functools.partial(SwiGLU, units, hidden_size, prefix="ffn_")
        sparse = functools.partial(
            SparseMoE, units, moe_hidden_size, num_experts, top_k,
            experts_held=experts_held,
            shared_hidden=moe_hidden_size * num_shared_experts,
            routed_scale=routed_scale, norm_topk=norm_topk, prefix="moe_")

        hc = {} if hc_mult is None else dict(
            hc_mult=hc_mult, hc_sinkhorn_iters=hc_sinkhorn_iters,
            hc_eps=hc_eps, hc_clamp=hc_clamp)
        self._streams = hc_mult

        def cell(i, prefix, kind="full_attention"):
            mixer, name = mixers[kind]
            return MLADecoderCell(units, mixer,
                                  dense if i < first_dense else sparse,
                                  epsilon=epsilon, prefix=prefix,
                                  mixer=name, **hc)
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            self.cells = HybridSequential(prefix="")
            for i in range(num_layers):
                self.cells.add(cell(i, f"layer{i}_", kinds[i]))
            self.final_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                      prefix="final_norm_")
            self.head = Dense(vocab_size, use_bias=False, flatten=False,
                              in_units=units, prefix="head_")
            self.mtp = MTPModule(
                units, functools.partial(cell, first_dense),
                epsilon=epsilon, prefix="mtp_", hc_mult=hc_mult) \
                if num_mtp else None
        if hc_mult is not None:
            from ...observability.registry import registry
            for name, value, doc in (
                    ("streams", hc_mult, "residual streams a token"),
                    ("sinkhorn_iters", hc_sinkhorn_iters, "Sinkhorn-Knopp "
                     "steps of a sublayer's H_res"),
                    ("sublayers", 2 * (num_layers + num_mtp), "sublayers "
                     "inside a hyper-connection")):
                registry().gauge(f"hc.{name}", doc + ", model last built") \
                    .set(value)

    @property
    def remat_blocks(self):
        return list(self.cells) + ([self.mtp] if self.mtp is not None else [])

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        if self._streams is not None:
            x = _spread(F, x, self._streams)
            from ...observability.registry import registry
            registry().gauge(
                "hc.saved_stream_bytes", "bytes of the streams that enter "
                "the main stack's blocks, what they keep when "
                "rematerialised, model last traced").set(
                len(self.cells) * x.size * x.dtype.itemsize)
        for cell in self.cells:
            x = cell(x)
        if self._streams is not None:
            x = _merge(F, x, self._streams)
        logits = self.head(self.final_norm(x))
        if self.mtp is None:
            return logits
        nxt = F.concat(F.slice_axis(tokens, axis=1, begin=1, end=None),
                       F.slice_axis(tokens, axis=1, begin=0, end=1), dim=1)
        return logits, self.head(self.mtp(x, self.embed(nxt)))


# ---------------------------------------------------------------------------
# Linear attention beside full attention: the post-norm hybrid decoder of the
# OLMo line (three gated delta-rule blocks to one attention block)
# ---------------------------------------------------------------------------

def _uniform(shape, low, high):
    import numpy as np
    import jax.random as jr
    from ... import random as _grandom
    return np.asarray(jr.uniform(_grandom.next_key(), shape, minval=low,
                                 maxval=high))


def _init_a_log(_, arr):
    """``A_log = log U(1, 16)``: each head forgets at a rate of its own."""
    import numpy as np
    arr[:] = np.log(_uniform(arr.shape, 1.0, 16.0))


def _init_dt_bias(_, arr):
    """``softplus(dt_bias) = exp(U(log 0.001, log 0.1))``."""
    import numpy as np
    dt = np.exp(_uniform(arr.shape, math.log(0.001), math.log(0.1)))
    arr[:] = dt + np.log(-np.expm1(-dt))


class GatedDeltaNet(HybridBlock):
    """Gated delta-rule mixer (Gated DeltaNet), causal, for training (no
    cache): ``q``, ``k`` and ``v`` each go through a depthwise causal
    convolution of ``conv_size`` taps and SiLU; per head ``q`` and ``k`` are
    l2-normalised (``q`` also divided by ``sqrt(key_dim)``), ``beta =
    sigmoid(x Wb)``, doubled where ``allow_neg_eigval``, ``g = -exp(A_log)
    softplus(x Wa + dt_bias)``; ``F.gated_delta_rule`` carries a state of
    (key_dim, value_dim) a head over the tokens; each head's output goes
    through one shared RMSNorm of ``value_dim`` and an output gate
    ``SiLU(x Wg)``, then the output projection."""

    def __init__(self, units, num_heads, key_dim, value_dim, conv_size=4,
                 allow_neg_eigval=True, epsilon=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._h, self._dk, self._dv = num_heads, key_dim, value_dim
        self._beta_scale = 2.0 if allow_neg_eigval else 1.0

        def lin(out, inp, name):
            return Dense(out, use_bias=False, flatten=False, in_units=inp,
                         prefix=name)
        with self.name_scope():
            self.q = lin(num_heads * key_dim, units, "q_")
            self.k = lin(num_heads * key_dim, units, "k_")
            self.v = lin(num_heads * value_dim, units, "v_")
            self.gate = lin(num_heads * value_dim, units, "gate_")
            self.a = lin(num_heads, units, "a_")
            self.b = lin(num_heads, units, "b_")
            self.q_conv = self.params.get(
                "q_conv", shape=(num_heads * key_dim, conv_size))
            self.k_conv = self.params.get(
                "k_conv", shape=(num_heads * key_dim, conv_size))
            self.v_conv = self.params.get(
                "v_conv", shape=(num_heads * value_dim, conv_size))
            self.a_log = self.params.get("a_log", shape=(num_heads,),
                                         init=_init_a_log)
            self.dt_bias = self.params.get("dt_bias", shape=(num_heads,),
                                           init=_init_dt_bias)
            self.o_norm = RMSNorm(epsilon=epsilon, in_channels=value_dim,
                                  prefix="o_norm_")
            self.proj = lin(units, num_heads * value_dim, "proj_")

    def hybrid_forward(self, F, x, q_conv, k_conv, v_conv, a_log, dt_bias):
        import jax
        b, s = x.shape[0], x.shape[1]
        h, dk, dv = self._h, self._dk, self._dv
        q, k, v = self.q(x), self.k(x), self.v(x)

        def short(t, taps, width):
            return F.reshape(F.silu(F.causal_conv1d(t, taps)),
                             shape=(b, s, h, width))

        def l2norm(t):
            return t * F.rsqrt(F.sum(F.square(t), axis=-1, keepdims=True)
                               + 1e-6)
        with jax.named_scope("conv"):
            q, k, v = short(q, q_conv, dk), short(k, k_conv, dk), \
                short(v, v_conv, dv)
            q, k = l2norm(q) * (1.0 / math.sqrt(dk)), l2norm(k)
        with jax.named_scope("decay"):
            beta = F.sigmoid(self.b(x)) * self._beta_scale
            g = -F.exp(a_log) * F.Activation(self.a(x) + dt_bias,
                                             act_type="softrelu")
        o = F.gated_delta_rule(q, k, v, g, beta)
        gate = F.reshape(self.gate(x), shape=(b, s, h, dv))
        with jax.named_scope("norm"):
            o = self.o_norm(o) * F.silu(gate)
        return self.proj(F.reshape(o, shape=(b, s, h * dv)))


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention (KDA, Kimi Linear), causal, for training (no
    cache): the delta rule with a decay a key channel.  ``q``, ``k`` and
    ``v`` each go through a depthwise causal convolution of ``conv_size``
    taps and SiLU; per head ``q`` and ``k`` are l2-normalised (``q`` also
    divided by ``sqrt(head_dim)``); ``beta = sigmoid(x Wb)``; the log-decay
    of each of a head's ``head_dim`` key channels is ``g = -exp(A_log[h])
    softplus(x Wfa Wfb + dt_bias)``, through a bottleneck of ``head_dim``;
    ``F.gated_delta_rule`` with that (B, L, H, dk) gate decays the state
    and then updates it; each head's output goes through one shared RMSNorm
    of ``head_dim`` times ``sigmoid`` of a low-rank gate ``x Wga Wgb + b``,
    then the output projection."""

    def __init__(self, units, num_heads, head_dim, conv_size=4, epsilon=1e-5,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._h, self._d = num_heads, head_dim
        width = num_heads * head_dim

        def lin(out, inp, name, bias=False):
            return Dense(out, use_bias=bias, flatten=False, in_units=inp,
                         prefix=name)
        with self.name_scope():
            self.q, self.k, self.v = (lin(width, units, n + "_")
                                      for n in "qkv")
            self.f_a = lin(head_dim, units, "f_a_")
            self.f_b = lin(width, head_dim, "f_b_")
            self.b = lin(num_heads, units, "b_")
            self.g_a = lin(head_dim, units, "g_a_")
            self.g_b = lin(width, head_dim, "g_b_", bias=True)
            self.q_conv, self.k_conv, self.v_conv = (
                self.params.get(n + "_conv", shape=(width, conv_size))
                for n in "qkv")
            self.a_log = self.params.get("a_log", shape=(num_heads,),
                                         init=_init_a_log)
            self.dt_bias = self.params.get("dt_bias", shape=(width,),
                                           init=_init_dt_bias)
            self.o_norm = RMSNorm(epsilon=epsilon, in_channels=head_dim,
                                  prefix="o_norm_")
            self.proj = lin(units, width, "proj_")

    def hybrid_forward(self, F, x, q_conv, k_conv, v_conv, a_log, dt_bias):
        import jax
        b, s = x.shape[0], x.shape[1]
        h, d = self._h, self._d

        def heads(t):
            return F.reshape(t, shape=(b, s, h, d))

        def l2norm(t):
            return t * F.rsqrt(F.sum(F.square(t), axis=-1, keepdims=True)
                               + 1e-6)

        def operands(x):
            q, k, v = self.q(x), self.k(x), self.v(x)
            with jax.named_scope("conv"):
                q, k, v = (heads(F.silu(F.causal_conv1d(t, taps)))
                           for t, taps in ((q, q_conv), (k, k_conv),
                                           (v, v_conv)))
                q, k = l2norm(q) * (1.0 / math.sqrt(d)), l2norm(k)
            with jax.named_scope("decay"):
                beta = F.sigmoid(self.b(x))
                g = -F.reshape(F.exp(a_log), shape=(1, 1, h, 1)) \
                    * F.Activation(heads(self.f_b(self.f_a(x)) + dt_bias),
                                   act_type="softrelu")
            return q, k, v, g, beta

        def output(x, o):
            gate = heads(self.g_b(self.g_a(x)))
            with jax.named_scope("norm"):
                o = self.o_norm(o) * F.sigmoid(gate)
            return self.proj(F.reshape(o, shape=(b, s, h * d)))

        def mix(x):
            return _checkpointed(output, x, F.gated_delta_rule(
                *_checkpointed(operands, x)))
        return _checkpointed(mix, x)


def _checkpointed(fn, *args):
    """``fn(*args)`` (NDArrays in; an NDArray or a tuple of them out) under
    ``jax.checkpoint`` where a program is being traced: the backward keeps
    ``args`` and makes the rest again; an eager call runs ``fn`` as it is.
    The KDA mixer is one (its inputs alone are kept while the rest of its
    block is differentiated) made of two more round the delta rule (the
    projections, convolutions and norms before it, the gated norm and the
    output projection after it), so that the backward of a block holds one
    stage's temporaries at a time: at 16,384 tokens each stage holds
    several (16,384 x 4096) float32 arrays, and all of them at once do
    not fit beside the expert layer's."""
    from ..block import _TraceCtx
    from ...ndarray import NDArray
    if _TraceCtx.active() is None:
        return fn(*args)
    import jax
    ctx = args[0].context

    def pure(*vals):
        out = fn(*(NDArray(v, ctx=ctx) for v in vals))
        return tuple(o._read() for o in out) if isinstance(out, tuple) \
            else out._read()
    out = jax.checkpoint(pure)(*(a._read() for a in args))
    return tuple(NDArray(o, ctx=ctx) for o in out) \
        if isinstance(out, tuple) else NDArray(out, ctx=ctx)


class QKNormAttention(HybridBlock):
    """Causal multi-head attention with an RMSNorm over all heads' lanes of
    ``q`` and of ``k`` before the split, and no position embedding; for
    training (no cache).  Through the flash kernel where it serves."""

    def __init__(self, units, num_heads, epsilon=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if units % num_heads:
            raise MXNetError(f"units {units} do not divide by {num_heads} "
                             "heads")
        self._heads = num_heads

        def lin(name):
            return Dense(units, use_bias=False, flatten=False,
                         in_units=units, prefix=name)
        with self.name_scope():
            self.q, self.k, self.v = lin("q_"), lin("k_"), lin("v_")
            self.q_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                  prefix="q_norm_")
            self.k_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                  prefix="k_norm_")
            self.proj = lin("proj_")

    def hybrid_forward(self, F, x):
        import jax
        q, k, v = self.q(x), self.k(x), self.v(x)
        with jax.named_scope("qk_norm"):
            q, k = self.q_norm(q), self.k_norm(k)
        return self.proj(_causal_attention(
            F, q, k, v, 1.0 / math.sqrt(x.shape[2] // self._heads),
            heads=self._heads))


class HybridDecoderCell(HybridBlock):
    """Post-norm block: ``h = x + RMSNorm(Mixer(x))``, then ``h +
    RMSNorm(SwiGLU(h))``; ``mixer`` builds the block's mixer, of either
    kind."""

    def __init__(self, units, mixer, hidden_size, epsilon=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.mixer = mixer()
            self.mixer_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                      prefix="mixer_norm_")
            self.ffn = SwiGLU(units, hidden_size, prefix="ffn_")
            self.ffn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                    prefix="ffn_norm_")

    def hybrid_forward(self, F, x):
        x = x + self.mixer_norm(self.mixer(x))
        return x + self.ffn_norm(self.ffn(x))


class OlmoHybridLM(HybridBlock):
    """Causal language model whose blocks are given by ``layer_types``:
    ``"linear_attention"`` is a ``GatedDeltaNet`` block, ``"full_attention"``
    a ``QKNormAttention`` block, each followed by a SwiGLU of
    ``hidden_size``, post-norm; a final RMSNorm and an untied head.
    ``net(tokens)`` returns the logits (B, S, vocab); ``remat_blocks`` lists
    the blocks a trainer rematerialises."""

    def __init__(self, vocab_size, units, layer_types, num_heads, hidden_size,
                 linear_num_heads, linear_key_head_dim, linear_value_head_dim,
                 linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
                 epsilon=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        import functools
        mixers = {
            "linear_attention": functools.partial(
                GatedDeltaNet, units, linear_num_heads, linear_key_head_dim,
                linear_value_head_dim, conv_size=linear_conv_kernel_dim,
                allow_neg_eigval=linear_allow_neg_eigval, epsilon=epsilon,
                prefix="gdn_"),
            "full_attention": functools.partial(
                QKNormAttention, units, num_heads, epsilon=epsilon,
                prefix="attn_")}
        for i, kind in enumerate(layer_types):
            if kind not in mixers:
                raise MXNetError(f"layer_types[{i}] = {kind!r} is neither of "
                                 f"{sorted(mixers)}")
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            self.cells = HybridSequential(prefix="")
            for i, kind in enumerate(layer_types):
                self.cells.add(HybridDecoderCell(
                    units, mixers[kind], hidden_size, epsilon=epsilon,
                    prefix=f"layer{i}_"))
            self.final_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                      prefix="final_norm_")
            self.head = Dense(vocab_size, use_bias=False, flatten=False,
                              in_units=units, prefix="head_")

    @property
    def remat_blocks(self):
        return list(self.cells)

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        for cell in self.cells:
            x = cell(x)
        return self.head(self.final_norm(x))


# ---------------------------------------------------------------------------
# Window and full attention over grouped-query heads, a router that stands
# before attention: the pre-norm sparse-expert decoder of the SmallThinker
# line (three window blocks with rotary positions to one full block with
# none)
# ---------------------------------------------------------------------------

class GroupedQueryAttention(HybridBlock):
    """Causal attention whose ``num_heads`` query heads of ``head_dim``
    lanes read ``num_kv_heads`` key and value heads, head h the key head
    ``h // (num_heads / num_kv_heads)``; no bias; for training (no cache).
    ``window``: a query weighs its own key and the ``window - 1`` before
    it (None: every key before it).  ``rope_theta``: rotate-half rotary
    positions over a head's whole width, from 0 (None: no position).  q, k
    and v are separate projections, so the flash kernel reads each where
    it lies and a group's key head in place."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 window=None, rope_theta=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_heads} query heads do not divide into "
                             f"groups over {num_kv_heads} key heads")
        self._heads, self._kv, self._d = num_heads, num_kv_heads, head_dim
        self._window, self._theta = window, rope_theta

        def lin(out, inp, name):
            return Dense(out, use_bias=False, flatten=False, in_units=inp,
                         prefix=name)
        with self.name_scope():
            self.q = lin(num_heads * head_dim, units, "q_")
            self.k = lin(num_kv_heads * head_dim, units, "k_")
            self.v = lin(num_kv_heads * head_dim, units, "v_")
            self.proj = lin(units, num_heads * head_dim, "proj_")

    def _rope(self, F, t, heads):
        b, s = t.shape[0], t.shape[1]
        return F.reshape(F.rope(F.reshape(t, shape=(b, s, heads, self._d)),
                                base=self._theta, seq_axis=1),
                         shape=(b, s, heads * self._d))

    def hybrid_forward(self, F, x):
        import jax
        q, k, v = self.q(x), self.k(x), self.v(x)
        if self._theta is not None:
            with jax.named_scope("rope"):
                q, k = self._rope(F, q, self._heads), \
                    self._rope(F, k, self._kv)
        # the kernel reads a group's key head in place where a head is a
        # whole lane group; another width goes through XLA
        return self.proj(_causal_attention(
            F, q, k, v, 1.0 / math.sqrt(self._d), heads=self._heads,
            kernel_serves=self._heads == self._kv or self._d % 128 == 0,
            kv_heads=self._kv, window=self._window))


class WindowMoEDecoderCell(HybridBlock):
    """Pre-norm block whose router stands before attention: the gates are
    made from the block's input ``x`` itself (before any norm), then ``h =
    x + Attention(RMSNorm(x))`` and ``h + Experts(RMSNorm(h))`` with those
    gates; ``attention`` and ``moe`` build the two."""

    def __init__(self, units, attention, moe, epsilon=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.attn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                     prefix="attn_norm_")
            self.attn = attention(prefix="attn_")
            self.ffn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                    prefix="ffn_norm_")
            self.moe = moe(prefix="moe_")

    def hybrid_forward(self, F, x):
        h = x + self.attn(self.attn_norm(x))
        return h + self.moe(self.ffn_norm(h), x)


class WindowMoELM(HybridBlock):
    """Causal language model of ``WindowMoEDecoderCell`` blocks, one a
    entry of the two lists: block l attends within ``window`` keys where
    ``sliding_window_layout[l]`` is 1 (else over every key before it) and
    carries rotary positions where ``rope_layout[l]`` is 1 (else none).
    Every block's feed-forward is a ``parallel.moe.SparseMoE`` with a
    softmax top-k router that reads the block's input, ReLU-gated experts
    of ``moe_hidden_size``, ``experts_held`` of the ``num_experts`` (one
    chip's share) and no shared expert; a final RMSNorm and an untied head.
    ``net(tokens)`` returns the logits (B, S, vocab); ``remat_blocks`` lists
    the blocks a trainer rematerialises."""

    def __init__(self, vocab_size, units, num_heads, num_kv_heads, head_dim,
                 moe_hidden_size, num_experts, top_k, experts_held,
                 sliding_window_layout, rope_layout, window, rope_theta,
                 epsilon=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        import functools
        from ...observability.registry import registry
        from ...parallel.moe import SparseMoE
        if len(sliding_window_layout) != len(rope_layout):
            raise MXNetError(
                f"sliding_window_layout names {len(sliding_window_layout)} "
                f"blocks and rope_layout {len(rope_layout)}")
        moe = functools.partial(
            SparseMoE, units, moe_hidden_size, num_experts, top_k,
            experts_held=experts_held, score="softmax", activation="relu")
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            self.cells = HybridSequential(prefix="")
            for i, (windowed, rotary) in enumerate(zip(sliding_window_layout,
                                                       rope_layout)):
                self.cells.add(WindowMoEDecoderCell(
                    units, functools.partial(
                        GroupedQueryAttention, units, num_heads, num_kv_heads,
                        head_dim, window=window if windowed else None,
                        rope_theta=rope_theta if rotary else None),
                    moe, epsilon=epsilon, prefix=f"layer{i}_"))
            self.final_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                      prefix="final_norm_")
            self.head = Dense(vocab_size, use_bias=False, flatten=False,
                              in_units=units, prefix="head_")
        windowed = sum(1 for w in sliding_window_layout if w)
        for name, value in (("window_layers", windowed),
                            ("full_layers",
                             len(sliding_window_layout) - windowed)):
            registry().gauge(f"lm.{name}", "blocks of the last window / "
                             "full attention model built").set(value)

    @property
    def remat_blocks(self):
        return list(self.cells)

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        for cell in self.cells:
            x = cell(x)
        return self.head(self.final_norm(x))
