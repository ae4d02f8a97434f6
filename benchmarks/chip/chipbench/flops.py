"""Operations and bytes the algorithm needs, from a configuration's shapes.

Never read from HLO: what the compiler emits includes recomputation and
work on dead cache positions, which a faster program is free to skip.
"""
from __future__ import annotations

from typing import Iterable

BF16 = 2


def dense_layer_matmul_params(cfg) -> int:
    """Weights of one dense decoder layer that take part in matmuls."""
    d, H, K, hd, f = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                      cfg.head_dim, cfg.d_ff)
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f


def dense_decode_flops(cfg, contexts: Iterable[int]) -> float:
    """FLOPs of one decode step for live slots attending ``contexts``
    positions each (their new one included): the layers' and the output
    projection's matmuls, and attention's two products over the live
    context.  Dead slots and dead cache positions are not counted."""
    ctx = list(contexts)
    per_token = 2 * (cfg.n_layers * dense_layer_matmul_params(cfg)
                     + cfg.d_model * cfg.vocab_size)
    attn = 4 * cfg.n_layers * cfg.n_heads * cfg.head_dim
    return float(len(ctx) * per_token + attn * sum(ctx))


def dense_decode_bytes(cfg, contexts: Iterable[int],
                       dtype_bytes: int = BF16) -> float:
    """HBM bytes one decode step needs: every layer weight and the output
    projection read once, the embedding rows of the live slots, the keys
    and values of each live slot's live positions read, and its one new
    position written."""
    ctx = list(contexts)
    d, L = cfg.d_model, cfg.n_layers
    weights = (L * (dense_layer_matmul_params(cfg) + 2 * d) + d
               + d * cfg.vocab_size) * dtype_bytes
    embed_rows = len(ctx) * d * dtype_bytes
    kv_pos = 2 * L * cfg.n_kv_heads * cfg.head_dim * dtype_bytes
    return float(weights + embed_rows + kv_pos * (sum(ctx) + len(ctx)))


def ssd_train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward FLOPs per token of a Mamba-2 model, from its
    shapes: 3x the forward (recomputation not counted).  Forward per token
    and layer: the in/out projections and the depthwise conv; the SSD
    mixer's chunked form (C.B scores and their weighted sum over the causal
    half of a chunk, then the chunk states in and out); the output
    projection over the vocabulary."""
    s = cfg.ssd
    d = cfg.d_model
    di = s.expand * d
    H = di // s.head_dim
    G, N, P, W = s.n_groups, s.d_state, s.head_dim, s.conv_width
    L = min(s.chunk_size, seq)
    conv_dim = di + 2 * G * N
    proj = 2 * (d * (2 * di + 2 * G * N + H) + di * d) + 2 * W * conv_dim
    intra = 2 * (L / 2) * (G * N + H * P)
    states = 2 * 2 * H * P * N
    fwd = cfg.n_layers * (proj + intra + states) + 2 * d * cfg.vocab_size
    return 3.0 * fwd
