"""Mean wait of a closed segment in the dispatcher's queue (enqueue to
dispatch), over the segments dispatched in the window, from the
dispatcher's own counters."""


def read(run):
    n = run.delta("queue_wait_count")
    return 1e3 * run.delta("queue_wait_total_s") / n if n else None
