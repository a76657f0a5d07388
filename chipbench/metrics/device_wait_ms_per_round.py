"""device_wait_ms_per_round: milliseconds per round over the window that
the engine waited for the device at retire (``block_until_ready``), from
``stats()["stage_walls"]["device_wait_s"]``."""


def read(run):
    rounds = run.stat_delta("rounds")
    if "device_wait_s" not in run.stats1["stage_walls"] or rounds <= 0:
        return None
    return 1e3 * run.stage_delta("device_wait_s") / rounds
