"""Device time of one run of the sharded IVF-Flat search program, in ms:
the "XLA Modules" events of ``jit__mnmg_flat_search`` that lie wholly in
the traced window, over their count, the mean over the chips."""

PROGRAM = "jit__mnmg_flat_search"


def read(rec, tr, peak):
    if tr is None or not tr.module_runs.get(PROGRAM):
        return None
    return tr.module_s[PROGRAM] / tr.module_runs[PROGRAM] * 1e3
