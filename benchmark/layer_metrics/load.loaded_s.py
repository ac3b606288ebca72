"""The server's own count from its main to serving ("model loaded in"):
checkpoint load, engine build, its warm admission."""


def read(ctx):
    return ctx["loaded_s"]
