"""bank_loads_per_kreq: context loads into the ContextBank per thousand
requests delivered over the window; each load is a real context write
(the bank's ``hits`` counter counts some lookups twice, so no hit ratio
is built on it)."""


def read(run):
    delivered = run.stat_delta("requests")
    if delivered <= 0:
        return None
    return 1e3 * run.stat_delta("loads") / delivered
