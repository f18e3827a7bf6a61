"""``train_hbm_gib``: the timed executable's planned peak per device, from
``compiled.memory_analysis()``: arguments + outputs + temporaries − aliased
bytes.  The compiler's plan, not a measurement."""


def read(art):
    if art.get("kind") != "train":
        return None
    return art.get("hbm_gib")
