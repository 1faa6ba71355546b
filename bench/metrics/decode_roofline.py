"""Share of the roofline reached by the stage decode programs: the least
time the chip could take for the window's decode dispatches, the larger of
the bytes they need over peak bandwidth and the operations over peak
compute, over their traced device time. Each dispatch reads its stage's
weights once, and each session step its own bytes beyond them and its
operations, as the layer module counts them (``arch/<name>.py``
``stage_weight_bytes``, ``decode_bytes``, ``decode_stage_flops``).
Dispatches and steps per stage are the executors' counters."""
from lib import measure


def read(ctx):
    v = measure.labelled(ctx, "decode")
    pos = measure.decode_positions(ctx.window)
    if v is None or ctx.peaks is None or not pos or v["s"] <= 0:
        return None
    arch, s, stages = ctx.arch, ctx.sizes, ctx.stages
    mean_pos = int(sum(pos) / len(pos))
    batches = measure.stage_deltas(ctx.window, "decode_batches")
    steps = measure.stage_deltas(ctx.window, "decode_steps")
    nbytes = flops = 0.0
    for stage in range(stages):
        weights = arch.stage_weight_bytes(s, stage, stages)
        nbytes += batches[stage] * weights
        nbytes += steps[stage] * (arch.decode_bytes(s, stage, stages,
                                                    [mean_pos]) - weights)
        flops += steps[stage] * arch.decode_stage_flops(s, stage, stages,
                                                        mean_pos)
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                flops / ctx.peaks["bf16_flops"])
    # the counted dispatches' least time against the named calls' time,
    # scaled to the same dispatches
    return 100.0 * least / (v["s"] * v["counted"] / v["calls"])
