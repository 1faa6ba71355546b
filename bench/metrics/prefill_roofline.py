"""Share of the roofline reached by the stage prefill programs: for each
prompt prefilled in the traced window, the least time the chip could take
for it, the larger of its operations over peak compute and its bytes over
peak bandwidth, summed over the prompts and divided by the device time of
the window's prefill programs, every stage (scaled to the dispatches the
executors counted).

Only the work a prompt needs counts, as the layer module counts it
(``arch/<name>.py``): operations are ``prefill_flops`` (every layer over the
real positions, causal pairs only, the logits of the last position only);
bytes are every stage's weights read once (``stage_weight_bytes``) and the
K and V the prompt writes, the growth of ``decode_kv_bytes`` by one
position times the prompt's length. Padding, masked-out pairs and logits
rows nobody reads show as lost share."""
from lib import measure


def read(ctx):
    v = measure.labelled(ctx, "prefill")
    prompts = measure.prompts_prefilled(ctx.window)
    if v is None or ctx.peaks is None or not prompts or v["s"] <= 0:
        return None
    arch, s, stages = ctx.arch, ctx.sizes, ctx.stages
    weights = sum(arch.stage_weight_bytes(s, st, stages)
                  for st in range(stages))
    kv_per_position = sum(arch.decode_kv_bytes(s, st, stages, 1)
                          - arch.decode_kv_bytes(s, st, stages, 0)
                          for st in range(stages))
    least = sum(max(arch.prefill_flops(s, n) / ctx.peaks["bf16_flops"],
                    (weights + kv_per_position * n)
                    / ctx.peaks["hbm_bytes_per_s"])
                for n in prompts)
    return 100.0 * least / (v["s"] * v["counted"] / v["calls"])
