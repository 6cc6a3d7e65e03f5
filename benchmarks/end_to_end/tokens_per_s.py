"""Global tokens per second of the window, all chips of the cell together:
every step dispatched in the window times the tokens of a step, over the
window's wall time from the first dispatch to the last loss on the host
(host clock, one step in flight)."""


def reduce(trace, run):
    return run["units_per_s"]
