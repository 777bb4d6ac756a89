"""Seconds from process start to the window's opening: imports, engine
build with the seeded weights, warm-up of every program the mix reaches
(compiling, or loading from the persistent cache), and the lead-in."""


def read(run):
    return run["setup_s"]
