"""How unevenly a batch's probes fall on the chips that own the lists:
the busiest chip's (query, row) pairs to scan over the mean chip's, the
mean over the window's batches (the driver's ``work``, from each batch's
exact global probes and the index's list ownership). 1 is even; the
slowest chip sets the pace of each run."""


def read(rec, tr, peak):
    return rec.get("work", {}).get("probe_skew")
