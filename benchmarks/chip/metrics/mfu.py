"""Model FLOP utilisation of the whole round: the local-training model
FLOPs of one round (three forward passes of every token the clients train
on, no recomputation; ``counts.model_flops_per_round``) over the traced
round time, the chips and their peak bf16 rate."""


def read(r):
    if (r.trace is None or not r.rounds or not r.trace.window_s
            or not r.peaks):
        return None
    round_s = r.trace.window_s / r.rounds
    return 100.0 * r.flops_per_round / (
        round_s * r.chips * r.peaks["peak_flops_bf16"])
