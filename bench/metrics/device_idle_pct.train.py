"""``device_idle_pct.train``: the share of the traced window of whole
training steps in which no operation ran on device 0."""


def read(art):
    if art.get("kind") != "train":
        return None
    tr = art["trace"]
    return tr.idle_pct(tr.devices[0])
