"""``eq7_adapt_ms``: device ms per step in the ``perfed.adapt`` scope: the
inner gradient on D_in and the adapted point w − α∇f (Eq. 7), from the traced
window of whole steps (``bench/scopes.py``)."""
from bench import scopes


def read(art):
    return scopes.phase_ms(art, "perfed.adapt")
