"""Share of the roofline reached by the stage decode programs: the least
time the chip could take for the window's decode dispatches, the larger of
the bytes they need over peak bandwidth and the operations over peak
compute, over their traced device time. Bytes: each dispatch reads its
stage's weights once, and each session step reads its cache positions and
writes one (lib.costs); the first stage also gathers one embedding row a
step. Dispatches and steps per stage are the executors' counters."""
from lib import costs, measure


def read(ctx):
    v = measure.labelled(ctx, "decode")
    pos = measure.decode_positions(ctx.window)
    if v is None or ctx.peaks is None or not pos or v["s"] <= 0:
        return None
    s, stages = ctx.sizes, ctx.stages
    mean_pos = int(sum(pos) / len(pos))
    batches = measure.stage_deltas(ctx.window, "decode_batches")
    steps = measure.stage_deltas(ctx.window, "decode_steps")
    nbytes = flops = 0.0
    for stage in range(stages):
        layers = costs.stage_layers(s, stages)[stage]
        nbytes += batches[stage] * costs.stage_weight_bytes(s, stage, stages)
        nbytes += steps[stage] * costs.decode_kv_bytes(s, stage, stages,
                                                       mean_pos)
        flops += steps[stage] * (2 * layers * costs.layer_params(s)
                                 + costs.attention_flops(s, layers,
                                                         mean_pos + 1))
        if stage == 0:
            nbytes += steps[stage] * s["d"] * 2
        if stage == stages - 1:
            flops += steps[stage] * 2 * s["d"] * s["v"]
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                flops / ctx.peaks["bf16_flops"])
    # the counted dispatches' least time against the named calls' time,
    # scaled to the same dispatches
    return 100.0 * least / (v["s"] * v["counted"] / v["calls"])
