"""Requests completed inside the window per second of the window."""


def read(run):
    return run.window.completed_in_window() / run.window.seconds
