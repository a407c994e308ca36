"""Live requests over bucket slots (``batches * max_batch``) in the window, in %."""


def read(run):
    b = run.stats["batches"]
    return run.stats["requests"] / (b * run.max_batch) * 100.0 if b else None
