"""Mean wait of a rig map from its close on the left camera to its
enqueue, for the right camera's frames to pass its end, over the maps
enqueued in the window, from the rig's own counters."""


def read(run):
    if "rig_holds" not in run.stats_close:
        return None
    n = run.delta("rig_holds")
    return 1e3 * run.delta("rig_hold_total_s") / n if n else None
