"""setup_s: process start to the window's start, in seconds.

Loading, building the catalog, compiling (or reading the compile cache)
and the warm-up traffic all count.
"""


def read(run):
    return run.setup_s
