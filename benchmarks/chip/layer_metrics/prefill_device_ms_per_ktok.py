"""Device time of whole-prompt prefill per 1,000 prompt tokens: the traced
``jit__prefill_one`` runs' total over the prompt tokens they prefilled, as
the harness recorded each call.  Layer: the model programs (models/)."""


def read(run):
    evs = run.trace.program_runs(("jit__prefill_one",))
    toks = sum(n for kind, _, n, _ in run.calls.prefill if kind == "prefill")
    if not evs or not toks:
        return None
    return sum(e.dur for e in evs) / 1e6 / (toks / 1000.0)
