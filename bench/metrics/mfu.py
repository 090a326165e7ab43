"""The whole step's share of the chips' bf16 peak (%): the model's matrix
FLOPs per tick (counts, once per tick however many chips repeat them) times
the ticks of the traced window, over its seconds, the chips and the peak."""


def read(rec):
    flops = rec.flops_per_tick * rec.ticks
    return 100.0 * flops / (rec.reduced.window_s * rec.chips * rec.peak["bf16_flops_per_s"])
