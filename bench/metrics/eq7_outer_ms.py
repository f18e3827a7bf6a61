"""``eq7_outer_ms``: device ms per step in the ``perfed.outer`` scope: the
gradient at the adapted point on D_o (Eq. 7), from the traced window of whole
steps (``bench/scopes.py``)."""
from bench import scopes


def read(art):
    return scopes.phase_ms(art, "perfed.outer")
