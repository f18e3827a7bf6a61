"""``eq7_update_ms``: device ms per step in the ``train.update`` scope: the
server update (clipping and the optimizer), from the traced window of whole
steps (``bench/scopes.py``)."""
from bench import scopes


def read(art):
    return scopes.phase_ms(art, "train.update")
