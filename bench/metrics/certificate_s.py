"""``certificate_s``: seconds per solve spent on ``flows()`` and
``min_cut()``, which run phase 2, from the benchmark's span
``certificate`` around those calls."""
import statistics


def read(run):
    spans = run.spans.get("certificate")
    return statistics.fmean(spans) if spans else None
