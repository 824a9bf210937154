"""setup_s: process start to the window's open: generating the corpus,
building the index, warming every shape the window uses (compiling, or
loading from the compile cache)."""


def read(run):
    return run.setup_s
