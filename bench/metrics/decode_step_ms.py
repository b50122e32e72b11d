"""Mean device time of one execution of the paged decode program, from the
trace's program (``XLA Modules``) events."""
from bench import trace as tr
from bench.readers import DECODE_PROGRAM


def read(run):
    if run.trace is None:
        return None
    t = tr.module_times_s(run.trace.mods, DECODE_PROGRAM)
    return 1e3 * sum(t) / len(t) if t else None
