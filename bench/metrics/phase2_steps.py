"""``phase2_steps``: phase 2's cancellation steps per traced solve, the
exact ``Solution.phase2_stats.steps`` of each solve; absent where the
program does not count them."""
import statistics


def read(run):
    steps = run.counts.get("phase2_steps")
    return statistics.fmean(steps) if steps else None
