"""``step_flops_ratio``: dot FLOPs the compiled step runs per device, over
the per-device Eq.-7 model FLOPs.  Above 1 is recomputed or discarded work.

The numerator counts the timed executable's HLO (``bench/hlo.py``, loop
trip counts applied); it repeats exactly from run to run.
"""


def read(art):
    if art.get("kind") != "train" or not art.get("hlo_dot_flops_step"):
        return None
    return art["hlo_dot_flops_step"] / (art["model_flops_step"]
                                        / art["chips"])
