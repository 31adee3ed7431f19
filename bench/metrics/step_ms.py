"""Engine step: the loop's host span around each engine step
(``bench.step``), summed over the window and divided by the steps, in ms."""


def read(w):
    if not w.steps:
        return None
    return 1000.0 * sum(b - a for a, b in w.steps) / len(w.steps)
