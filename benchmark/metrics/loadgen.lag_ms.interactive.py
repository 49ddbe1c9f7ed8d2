"""How late the open-loop generator ran: its worst lag behind a request's
due time, in ms (host clock), over the requests due before the tracer
started. A lag near the inter-arrival gap means the offered load was the
generator's, not the schedule's."""

import numpy as np


def read(rec, tr, peak):
    lg = rec.get("loadgen")
    if lg is None:
        return None
    lags = np.asarray(lg["lags_s"])
    until = rec.get("host_until")
    if until is not None:
        lags = lags[rec["requests"]["due"] < until]
    return float(lags.max(initial=0.0)) * 1e3
