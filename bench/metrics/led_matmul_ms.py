"""Device milliseconds of the fused LED kernel per engine step of the
traced slice: every ``led_matmul`` call of the factorized projections,
decode and prefill chunks alike.  A time, not a roofline share: XLA
prefetches the kernel's weight factors into on-chip memory while earlier
ops run, so the kernel's own events cover less than the bytes it needs.
Silent for a model without LED layers."""

import work


def read(run):
    steps = len(work.steps(run))
    seconds = work.kernel_s(run, work.LED)
    if not run.conf.get("factorize") or not steps or not seconds:
        return None
    return 1e3 * seconds / steps
