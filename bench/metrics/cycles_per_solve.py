"""``cycles_per_solve``: push-relabel cycles per cold solve, the exact
``Solution.stats.cycles`` of each solve of the traced part."""
import statistics


def read(run):
    cycles = run.counts.get("cycles")
    if not cycles or run.traffic["driver"] != "cold_solves":
        return None
    return statistics.fmean(cycles)
