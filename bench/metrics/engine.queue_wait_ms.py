"""Mean wait of a request in the serving engine's queue before a batch took
it: the window's sum / count of ``engine_queue_wait_seconds``."""


def read(ctx):
    total, count = ctx["engine"]["queue_wait"]
    return 1e3 * total / count if count else None
