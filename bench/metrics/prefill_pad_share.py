"""Share of the positions the stage prefill programs computed over the
window that were padding, all stages: 100 x (bucketed positions - real
positions) / bucketed positions (StageExecutor.stats ``prefill_positions``
and ``prefill_tokens``). Nothing where the program keeps no such
counters."""
from lib import measure


def read(ctx):
    if "prefill_positions" not in ctx.window.counters_end["executors"][0]:
        return None
    positions = measure.counter_delta(ctx.window, "prefill_positions")
    if positions <= 0:
        return None
    tokens = measure.counter_delta(ctx.window, "prefill_tokens")
    return 100.0 * (positions - tokens) / positions
