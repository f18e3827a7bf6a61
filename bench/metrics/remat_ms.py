"""``remat_ms``: device ms per step that ``jax.checkpoint`` recomputes
(``rematted_computation`` on the operation's scope path), in any phase, from
the traced window of whole steps (``bench/scopes.py``)."""
from bench import scopes


def read(art):
    return scopes.part_ms(art, "remat")
