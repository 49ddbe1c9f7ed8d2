"""The IVF-Flat scan kernel's share of its roofline inside the sharded
search program, in %: the least time the chip could take for the mean
chip's scan work of one batch, the mean over the window's batches (the
driver's ``work``: ``benchmark.roofline.ivf_scan_work`` over the lists
each chip owns among the batch's probes), over the device time of the
``flat_scan_subchunk_min`` events in one run of ``jit__mnmg_flat_search``,
the mean over the chips and over the runs that lie wholly in the traced
window."""

from benchmark import roofline

KERNEL = "flat_scan_subchunk_min"
PROGRAM = "jit__mnmg_flat_search"


def read(rec, tr, peak):
    work = rec.get("work", {}).get(KERNEL)
    if tr is None or work is None or not tr.module_runs.get(PROGRAM):
        return None
    per_run = tr.module_op_s.get(PROGRAM, {}).get(KERNEL)
    if not per_run:
        return None
    least, _bound = roofline.least_time(work[0], work[1], peak)
    return least / (per_run / tr.module_runs[PROGRAM]) * 100.0
