"""Device time of the collectives per run of the sharded IVF-Flat search
program, in ms: the collective ops of the traced window (the merge's
all-gather of each chip's (queries, k) answers), over the runs of
``jit__mnmg_flat_search`` that lie wholly in it, the mean over the
chips."""

PROGRAM = "jit__mnmg_flat_search"


def read(rec, tr, peak):
    if tr is None or not tr.module_runs.get(PROGRAM):
        return None
    return tr.collective_s / tr.module_runs[PROGRAM] * 1e3
