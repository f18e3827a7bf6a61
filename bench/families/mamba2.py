"""Family ``mamba2``: the program's Mamba-2 SSD stack (``models/ssm.py``).

``program_config`` gives the program's ``ModelConfig`` at the sizes a
configuration file states; ``forward_per_token`` the model FLOPs of one
forward pass per token, from those sizes (``bench/harness.py``).

Forward, per token (d model width, E = expand·d, H heads of P channels,
N state, Q chunk, V vocabulary, L layers), counting the multiply-adds of
matrix products and the SSD einsums (two FLOPs each) and nothing
elementwise:

* per layer: in_proj 2·d·(2E + 2N + H), out_proj 2·E·d; SSD scores
  C·Bᵀ within a chunk 2·Q·N, the intra-chunk output 2·Q·H·P, the chunk
  states 2·H·P·N and the inter-chunk output 2·H·P·N;
* once: the output head 2·d·V, V padded as the head holds it.
"""
from __future__ import annotations


def vocab_rows(cfg: dict) -> int:
    """The vocabulary padded to ``pad_vocab_size_multiple``, as the
    embedding and the head hold it."""
    m = cfg.get("pad_vocab_size_multiple", 1)
    return -(-cfg["vocab_size"] // m) * m


def program_config(cfg: dict):
    """The program's ``ModelConfig`` at the sizes ``cfg`` states."""
    import dataclasses

    from repro.config import SSMConfig
    from repro.configs import get_config

    base = get_config(cfg["program_config"])
    return dataclasses.replace(
        base, num_layers=cfg["n_layer"], d_model=cfg["d_model"],
        vocab_size=vocab_rows(cfg), tie_embeddings=cfg["tie_embeddings"],
        dtype=cfg["dtype"],
        ssm=SSMConfig(state_dim=cfg["d_state"], head_dim=cfg["headdim"],
                      expand=cfg["expand"], chunk_size=cfg["chunk_size"],
                      conv_width=cfg["d_conv"]))


def forward_per_token(cfg: dict, seq_len: int) -> float:
    """Per token, at any ``seq_len``: the SSD's work grows with the chunk,
    not with the sequence."""
    d, depth, vocab = cfg["d_model"], cfg["n_layer"], vocab_rows(cfg)
    e = cfg["expand"] * d
    h = e // cfg["headdim"]
    p, n, q = cfg["headdim"], cfg["ngroups"] * cfg["d_state"], \
        cfg["chunk_size"]
    proj = 2 * e + 2 * n + h
    layer = (2 * d * proj + 2 * e * d
             + 2 * q * n + 2 * q * h * p + 4 * h * p * n)
    return float(depth * layer + 2 * d * vocab)
