"""Mean share of the compiled batch that live requests filled: the window's
sum / count of ``engine_batch_occupancy_ratio``."""


def read(ctx):
    total, count = ctx["engine"]["occupancy"]
    return total / count if count else None
