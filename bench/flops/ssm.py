"""Model FLOPs of the PerFedS² step for the Mamba-2 family, from shapes.

Forward, per token (d model width, E = expand·d, H heads of P channels,
N state, Q chunk, V vocabulary, L layers), counting the multiply-adds of
matrix products and the SSD einsums (two FLOPs each) and nothing
elementwise:

* per layer: in_proj 2·d·(2E + 2N + H), out_proj 2·E·d; SSD scores
  C·Bᵀ within a chunk 2·Q·N, the intra-chunk output 2·Q·H·P, the chunk
  states 2·H·P·N and the inter-chunk output 2·H·P·N;
* once: the output head 2·d·V, V padded as the head holds it.

The Eq.-7 step, on T tokens per role (inner, outer, Hessian):

* a gradient is a forward (F) and a backward pass (2F: each product
  ``y = x·W`` costs ``dy·Wᵀ`` and ``xᵀ·dy``) — 3F;
* the inner gradient on D_in and the outer gradient at the adapted point on
  D_o are 3F each;
* the Hessian-vector product on D_h is forward-over-reverse: the primal
  gradient (3F) and its tangent, where every bilinear product ``a·b``
  gets ``da·b + a·db`` (6F) — 9F.

So one step is 15·T·F.  Not counted: recomputation under remat, the
second inner adaptation that ``perfed_loss`` runs for the reported loss,
and cohorts whose fresh gradient the step discards.
"""
from __future__ import annotations


def vocab_rows(cfg: dict) -> int:
    """The vocabulary padded to ``pad_vocab_size_multiple``, as the
    embedding and the head hold it."""
    m = cfg.get("pad_vocab_size_multiple", 1)
    return -(-cfg["vocab_size"] // m) * m


def forward_per_token(cfg: dict) -> float:
    d, depth, vocab = cfg["d_model"], cfg["n_layer"], vocab_rows(cfg)
    e = cfg["expand"] * d
    h = e // cfg["headdim"]
    p, n, q = cfg["headdim"], cfg["ngroups"] * cfg["d_state"], \
        cfg["chunk_size"]
    proj = 2 * e + 2 * n + h
    layer = (2 * d * proj + 2 * e * d
             + 2 * q * n + 2 * q * h * p + 4 * h * p * n)
    return float(depth * layer + 2 * d * vocab)


def eq7_step(cfg: dict, batch: int, seq_len: int) -> float:
    """Model FLOPs of one cohort's Eq.-7 meta-gradient."""
    return 15.0 * batch * seq_len * forward_per_token(cfg)
