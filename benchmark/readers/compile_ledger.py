"""What set-up spent compiling, from the program's own compile ledger
(`kungfu_tpu/compile_cache.py`), in the process the runner is.

Set-up's records are those up to and including the LAST record of the
train step's program (`STEP`, a pattern on the record's `fun`: every
train step `parallel/train.py` builds is `step` or `device_step`):
`warm_up` ends when the step stops compiling, the window holds no
compile, and what `verify` compiles afterwards is not set-up. A program
without a ledger (the parent of the PR that brought it) reads nothing:
the metric is left out and nothing raises."""

STEP = r"^(step|device_step)$"


def _setup_records(fun):
    from kungfu_tpu import compile_cache

    ledger = getattr(compile_cache, "ledger", lambda: None)()
    if ledger is None:
        return None
    return ledger.records(fun=fun, until_last=STEP)


def seconds(trace, ctx, phase, fun=None):
    """Seconds of `phase` (`trace`, `lower`, `backend`, or `all` three)
    summed over set-up's records whose `fun` matches `fun`."""
    records = _setup_records(fun)
    if records is None:
        return None
    keys = (("trace_s", "lower_s", "backend_s") if phase == "all"
            else (f"{phase}_s",))
    return [sum(r[k] for r in records for k in keys)]


def count(trace, ctx, what, fun=None):
    """`programs`: how many of set-up's records match `fun`; `misses`:
    how many of them missed the persistent cache."""
    records = _setup_records(fun)
    if records is None:
        return None
    if what == "misses":
        records = [r for r in records if r["cache"] == "miss"]
    return [len(records)]
