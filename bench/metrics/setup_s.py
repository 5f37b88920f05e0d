"""setup_s: process start to the first timed step: imports, warm-up of
every shape the window reaches (compiles included), KV generation and
the admission of the cell's sessions (host clock)."""


def read(run):
    return run.setup_s
