"""Seconds from the process's start to the window's start: data, loading,
compiling or reading compiled programs, training and warm-up."""


def read(run):
    return run.setup_s
