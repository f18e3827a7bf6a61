"""``unscoped_ms``: device ms per step in no phase scope, from the traced
window of whole steps (``bench/scopes.py``): the coverage guard of the
``eq7_*_ms`` split, which with it sums to the busy time."""
from bench import scopes


def read(art):
    return scopes.phase_ms(art, scopes.UNSCOPED)
