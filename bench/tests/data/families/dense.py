"""Family ``dense``: the program's decoder-only transformer
(``models/transformer.py``: GQA with RoPE, a SwiGLU MLP, RMSNorm), from a
Llama-style ``config.json``'s keys.

Forward FLOPs per token of a T-token sequence (d width, H query and K
key-value heads of D channels, F the MLP's width, V vocabulary, L layers;
two FLOPs a multiply-add, nothing elementwise):

* per layer: the projections 2·d·H·D (q), 2·2·d·K·D (k, v), 2·H·D·d (o);
  the scores and their weighted sum 2·2·T·H·D, over all T keys, as the
  program computes them under the causal mask; the MLP 3·2·d·F;
* once: the output head 2·d·V.
"""
from __future__ import annotations


def program_config(cfg: dict):
    """The program's ``ModelConfig`` at the sizes ``cfg`` states."""
    import dataclasses

    from repro.configs import get_config

    return dataclasses.replace(
        get_config(cfg["program_config"]),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=0,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"])


def forward_per_token(cfg: dict, seq_len: int) -> float:
    d, h, k = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_key_value_heads"])
    hd = d // h
    layer = (2 * d * h * hd + 4 * d * k * hd + 2 * h * hd * d
             + 4 * seq_len * h * hd + 6 * d * cfg["intermediate_size"])
    return float(cfg["num_hidden_layers"] * layer
                 + 2 * d * cfg["vocab_size"])
