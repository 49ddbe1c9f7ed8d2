"""Device time of one run of the grouped IVF-Flat search program, in ms:
the "XLA Modules" events of ``jit__grouped_impl`` that lie wholly in the
traced window, over their count."""

PROGRAM = "jit__grouped_impl"


def read(rec, tr, peak):
    if tr is None or not tr.module_runs.get(PROGRAM):
        return None
    return tr.module_s[PROGRAM] / tr.module_runs[PROGRAM] * 1e3
