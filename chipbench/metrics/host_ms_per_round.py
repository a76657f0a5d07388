"""host_ms_per_round: host milliseconds of the overlay's plan, assemble
and collect stages per round over the window, from the engine's
``stats()["stage_walls"]``.  ``execute_s`` is left out: it mixes launch
time with the host's wait for the device."""


def read(run):
    rounds = run.stat_delta("rounds")
    if rounds <= 0:
        return None
    host_s = sum(run.stage_delta(k)
                 for k in ("plan_s", "assemble_s", "collect_s"))
    return 1e3 * host_s / rounds
