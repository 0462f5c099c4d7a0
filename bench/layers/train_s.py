"""Planner and plan cache: the host seconds of the set-up's training serves
(plan enumeration and every candidate plan's measured runs), summed."""


def read(run):
    return sum(run.train_walls) if run.train_walls else None
