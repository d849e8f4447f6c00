"""95th percentile of the wait from a slot to the first token, in the
program's own accounting at the close of the window (``prefill_stats()
["prefill_wait_p95_s"]``): a request admitted and not yet bound enters
with its wait so far.  Silent for a program that does not count it."""


def read(run):
    v = run.counters.get("prefill_wait_p95_s")
    return None if v is None else 1e3 * v
