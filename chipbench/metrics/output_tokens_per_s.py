"""Output tokens committed by the steps that began in the window, over the
seconds from the window's opening to the end of its last step."""
from cb.measures import window_steps


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    secs = steps[-1].t1 - run["window"][0]
    return sum(s.n_tokens for s in steps) / secs
