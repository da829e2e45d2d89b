"""``csr_build_s``: seconds per solve spent building the problem and its
residual CSR (``MaxflowProblem(...).residual``), from the benchmark's
span ``csr_build`` around that call."""
import statistics


def read(run):
    spans = run.spans.get("csr_build")
    return statistics.fmean(spans) if spans else None
