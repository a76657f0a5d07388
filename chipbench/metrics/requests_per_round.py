"""requests_per_round: requests delivered per round over the window, from
the engine's ``delivered`` and ``rounds`` counters."""


def read(run):
    rounds = run.stat_delta("rounds")
    if rounds <= 0:
        return None
    return run.stat_delta("requests") / rounds
