"""95th percentile of the wait from submit to a slot, in the program's
own accounting at the close of the window (``prefill_stats()
["queue_wait_p95_s"]``): a request still queued enters with its wait so
far.  Silent for a program that does not count it."""


def read(run):
    v = run.counters.get("queue_wait_p95_s")
    return None if v is None else 1e3 * v
