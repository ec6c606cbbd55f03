"""Device time of one decode step: the mean duration of the traced
``jit__decode_all`` and ``jit__mixed`` program runs.  Layer: the model
programs (models/)."""

PROGRAMS = ("jit__decode_all", "jit__mixed")


def read(run):
    evs = run.trace.program_runs(PROGRAMS)
    if not evs:
        return None
    return sum(e.dur for e in evs) / len(evs) / 1e6
