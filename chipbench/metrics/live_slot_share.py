"""live_slot_share: the share, in percent, of the executor's instruction
slots over the window that ran live work: Σ elements x the kernel's live
instruction words, over Σ padded tiles x tile x stages x slots, from the
engine's ``slots_live`` and ``slots_executed``."""


def read(run):
    if "slots_executed" not in run.stats1:
        return None
    executed = run.stat_delta("slots_executed")
    if executed <= 0:
        return None
    return 100.0 * run.stat_delta("slots_live") / executed
