"""Share of admitted prompt tokens that the prefix cache served, so that
prefill skipped them: the engine's ``prefix_skipped_tokens`` over
``prompt_tokens_admitted`` in the window."""


def read(run):
    c = run.counters
    if not c.get("prompt_tokens_admitted"):
        return None
    return 100.0 * c["prefix_skipped_tokens"] / c["prompt_tokens_admitted"]
