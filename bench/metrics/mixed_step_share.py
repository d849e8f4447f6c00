"""Share of the engine's decode steps that also ran a prefill chunk, from
the warm-up's end to the close of the window (``prefill_stats()``
``mixed_steps`` over ``decode_steps``): such a step delays every stream's
next token by the chunk.  Silent for a program that does not count
them."""


def read(run):
    c = run.counters
    if "mixed_steps" not in c or not c.get("decode_steps"):
        return None
    return 100.0 * c["mixed_steps"] / c["decode_steps"]
