"""Seconds of ``PDASCIndex.build`` (MSA levels, k-medoids, swap sweeps and
the payload store), host clock, ending in ``block_until_ready``."""


def read(ctx):
    return ctx["build_s"]
