"""setup_s: seconds from the run's start to the window's opening:
plaintext data and schedule, keygen, ingest, index builds, the client
trapdoor pool and the warm-up (compilation included)."""


def read(ctx):
    return ctx.setup_s
