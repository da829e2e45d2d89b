"""``cycle_loop_ms.solve``: device milliseconds per solve of the cycle-loop
program (``pushrelabel.run_cycles``), from the profiler trace of the
traced solves."""

#: jit names of the programs that run a solve's cycles
PROGRAMS = ("jit_run_cycles",)


def read(run):
    secs = run.trace.program_s(PROGRAMS)
    solves = len(run.spans.get("solve", ()))
    if secs is None or not solves:
        return None
    return secs * 1e3 / solves
