"""Share of the dispatched batch rows that are padding: padded rows over
valid plus padded rows, from the executor's counters as they stood when
the tracer started."""


def read(rec, tr, peak):
    ex = rec.get("executor_host")
    if not ex or not ex["batches"]:
        return None
    return ex["padded_rows"] / (ex["valid_rows"] + ex["padded_rows"])
