"""``ssd_scan_ms``: device ms per step in the ``ssm.ssd`` scope (the chunked
SSD scan and its D skip term), in any phase, from the traced window of whole
steps (``bench/scopes.py``)."""
from bench import scopes


def read(art):
    return scopes.part_ms(art, "ssm.ssd")
