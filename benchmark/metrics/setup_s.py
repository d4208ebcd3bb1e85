"""setup_s: process start to the first timed step (imports, data from
the seed, program load or compile, warm-up), on the host clock."""


def read(ctx):
    return ctx["setup_s"]
