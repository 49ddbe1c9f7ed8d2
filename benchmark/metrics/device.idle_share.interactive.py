"""Share of the traced window in which no operation ran on the device,
in %: 1 - the union of the "XLA Ops" intervals over the window."""


def read(rec, tr, peak):
    return None if tr is None else tr.idle_share * 100.0
