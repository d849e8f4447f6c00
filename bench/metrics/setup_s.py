"""Seconds from the start of the process to the first due request:
making the weights, building the model and the engine, compiling or
loading every program and running it once."""


def read(run):
    return run.setup_s
