"""The whole round's share of the chips' bf16 peak: the model FLOPs of the
cohort's gradients (forward plus backward, from shapes) times the rounds
the traced window completed, over its length, chips and peak."""


def read(facts):
    flops = facts["model_flops_per_round"] * facts["rounds"]
    peak = facts["chips"] * facts["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / facts["trace"]["window_s"] / peak
