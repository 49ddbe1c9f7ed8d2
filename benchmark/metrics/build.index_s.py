"""Seconds of ``ivf_flat_build`` in set-up (host clock, ending in
``block_until_ready``): k-means, list splitting and the list-sorted copy
of the rows."""


def read(rec, tr, peak):
    return rec.get("build_s")
