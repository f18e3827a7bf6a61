"""``eq7_hvp_ms``: device ms per step in the ``perfed.hvp`` scope: the
Hessian-vector product on D_h and the Hessian term of Eq. 7, from the traced
window of whole steps (``bench/scopes.py``)."""
from bench import scopes


def read(art):
    return scopes.phase_ms(art, "perfed.hvp")
