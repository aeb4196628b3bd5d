"""Set-up time: process start to the start of the measured window
(imports, device start, traffic made, compilation or the compile cache,
warm-up calls). Seconds, host clock."""


def read(ctx):
    return ctx["setup_s"]
