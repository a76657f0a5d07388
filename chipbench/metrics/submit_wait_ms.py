"""submit_wait_ms: mean milliseconds a submit over the window waited for
the pump's engine lock, from the pump's ``submit_wait_s``."""


def read(run):
    submits = run.stat_delta("submits")
    if "submit_wait_s" not in run.stats1 or submits <= 0:
        return None
    return 1e3 * run.stat_delta("submit_wait_s") / submits
