"""``train_step_mfu``: the step's share of the chips' bf16 peak.

Eq.-7 model FLOPs of the cohorts a step refreshes
(``train_common.eq7_step`` over the family's ``forward_per_token``), times
the steps in the traced window, over window × chips × the published bf16
peak of the device (``bench/peaks.json``).
"""


def read(art):
    if art.get("kind") != "train" or not art.get("steps"):
        return None
    return 100.0 * art["model_flops_step"] * art["steps"] / (
        art["window_s"] * art["chips"] * art["peak_flops"])
