"""Transforms completed in the window over the window (host clock).

A closed loop's window runs from the answer that completes its first
bucket to the last answer of its drain, so it holds whole buckets."""


def read(run):
    return run.completed_in_window / run.window_s
