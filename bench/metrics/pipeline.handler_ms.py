"""Mean time of one handler call (query plan and search pipeline, results
on the host): the window's sum / count of ``engine_handler_seconds``."""


def read(ctx):
    total, count = ctx["engine"]["handler"]
    return 1e3 * total / count if count else None
