"""Process start to the first timed request: data from the seed, bring-up,
trace + compile (or cache load) of the cell's shapes, correctness pass."""


def read(run):
    return run["setup_s"]
