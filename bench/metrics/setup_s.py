"""setup_s: process start to the first timed unit (host clock): device
start-up, weights and feed made from the seed, the cell's programs
compiled or loaded from the compile cache, and one warm-up cycle."""


def read(run):
    return run.setup_s
