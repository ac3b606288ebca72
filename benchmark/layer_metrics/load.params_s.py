"""Checkpoint to device: the first part of the server's "model loaded
in" (``/debug/prof`` ``startup.params_s``; the others are ``engine_s``,
``warm_s`` and their total ``loaded_s``)."""


def read(ctx):
    return ctx["after"]["prof"].get("startup", {}).get("params_s")
