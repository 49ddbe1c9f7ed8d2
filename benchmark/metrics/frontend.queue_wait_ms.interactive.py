"""Mean wait of a request in the executor's queue before it is packed
into a batch, in ms: the sum over the count of the executor's
``queue_wait`` stage histogram (its log2 quantiles are too coarse), as it
stood when the tracer started."""


def read(rec, tr, peak):
    ex = rec.get("executor_host")
    if not ex or not ex["queue_wait_count"]:
        return None
    return ex["queue_wait_sum_ms"] / ex["queue_wait_count"]
