"""XLA compilations inside the measured window (``/debug/prof``
``compiles``). Want 0: warm-up is to have met every shape."""


def read(ctx):
    return ctx["after"]["prof"]["compiles"] - ctx["before"]["prof"]["compiles"]
