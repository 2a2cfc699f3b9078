"""Mean time a request waited in the admission queue: submission to
first admission into a slot (``ServeStats.queue_wait_s`` over
``ServeStats.admissions``; a preemption resume is not a new admission).
A program without the counters reports nothing."""


def read(ctx):
    s = ctx["stats"]
    wait = getattr(s, "queue_wait_s", None)
    n = getattr(s, "admissions", 0)
    if wait is None or not n:
        return None
    return 1000.0 * wait / n
