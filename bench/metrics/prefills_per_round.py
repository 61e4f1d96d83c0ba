"""Scheduler layer (``serve/engine.py``): requests that left the queue
(were prefilled) in each ``step()`` of the window, mean over steps."""


def read(run):
    if not run.steps:
        return None
    return sum(s.prefills for s in run.steps) / len(run.steps)
