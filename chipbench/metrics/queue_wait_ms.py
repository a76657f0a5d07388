"""queue_wait_ms: mean milliseconds a request delivered over the window
queued before its round launched, from the engine's ``queue_wait_s``
(submit to round launch, summed over delivered requests)."""


def read(run):
    delivered = run.stat_delta("requests")
    if "queue_wait_s" not in run.stats1 or delivered <= 0:
        return None
    return 1e3 * run.stat_delta("queue_wait_s") / delivered
