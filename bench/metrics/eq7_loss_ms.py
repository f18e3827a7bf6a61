"""``eq7_loss_ms``: device ms per step in the ``perfed.loss`` scope: the
reported meta-loss F(w) = f(w − α∇f(w; D_in); D_o), from the traced window of
whole steps (``bench/scopes.py``).  Work XLA shares with ``perfed.adapt``
goes by the scope the compiler keeps."""
from bench import scopes


def read(art):
    return scopes.phase_ms(art, "perfed.loss")
