"""Distinct matches whose replies reached the client inside the window,
over the window's seconds."""


def read(run):
    return run.matches_in_window / run.seconds
