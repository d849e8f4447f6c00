"""Programs compiled, or loaded from the compile cache, on the thread
that steps the engine, from the warm-up's end to the close of the window
(``prefill_stats()["programs_compiled"]``); 0 is what set-up promises.
Silent for a program that does not count them."""


def read(run):
    return run.counters.get("programs_compiled")
