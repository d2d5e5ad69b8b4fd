"""Operations and bytes from shapes: what a model step or a kernel call
needs, independent of how the program computes it.

Every count is of the work the algorithm requires: causal attention counts
the half of the score matrix at or below the diagonal, recomputation
(remat) is not counted, and a kernel's bytes are the operands it must
read and the results it must write once.
"""
from __future__ import annotations

from typing import Sequence


def layer_matmul_params(cfg) -> int:
    """Weights that enter a matrix product in one attention block."""
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    return attn + 3 * d * cfg.d_ff


def param_count(cfg) -> int:
    """Every parameter of a dense decoder: embedding, output head unless
    tied, each block's matmul weights and norm scales, the final norm."""
    d, V = cfg.d_model, cfg.vocab
    norm = 0 if cfg.norm == "nonparametric" else d
    total = V * d + (0 if cfg.tie_embeddings else V * d) + norm
    total += cfg.n_layers * (layer_matmul_params(cfg) + 2 * norm)
    return total


def forward_flops_per_token(cfg, context: float) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions
    (itself included): every matmul weight twice, the unembedding, and
    the QK and PV products."""
    mm = cfg.n_layers * layer_matmul_params(cfg) + cfg.d_model * cfg.vocab
    attn = cfg.n_layers * 4 * cfg.n_heads * cfg.head_dim * context
    return 2.0 * mm + attn


def prefill_flops(cfg, n: int) -> float:
    """Forward FLOPs of a causal prefill of ``n`` tokens."""
    return n * forward_flops_per_token(cfg, (n + 1) / 2.0)


def decode_flops(cfg, contexts: Sequence[int]) -> float:
    """Forward FLOPs of one decode step: one token per entry of
    ``contexts``, each attending to that many positions."""
    return sum(forward_flops_per_token(cfg, c) for c in contexts)


def train_flops(cfg, batch: int, seq: int) -> float:
    """FLOPs a training step needs: forward and backward (3x the forward)
    of ``batch`` causal sequences of ``seq`` tokens."""
    return 3.0 * batch * prefill_flops(cfg, seq)


def paged_attention_bytes(cfg, contexts: Sequence[int], page: int,
                          kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """HBM bytes one call of the paged decode kernel needs (one layer):
    every live K and V page of each request, its query row and its output
    row.  ``contexts`` are the positions each request attends to."""
    hkv, hd, hq = cfg.n_kv_heads, cfg.head_dim, cfg.n_heads
    page_bytes = page * hkv * hd * kv_bytes
    kv = sum(2 * (-(-c // page)) * page_bytes for c in contexts)
    qo = len(contexts) * 2 * hq * hd * act_bytes
    return float(kv + qo)


def paged_attention_flops(cfg, contexts: Sequence[int]) -> float:
    """FLOPs of one paged decode kernel call: QK and PV over each
    request's context."""
    return float(sum(4 * cfg.n_heads * cfg.head_dim * c for c in contexts))
