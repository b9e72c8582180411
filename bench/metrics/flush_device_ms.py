"""flush_device_ms: device ms per pump of the serve flush: the event
react program (`JaxEngine._react`, traced as `jit__react_impl`) plus
the eager scatters `set_votes` dispatches (`jit_scatter`), over the
traced pumps."""

PROGRAMS = ("jit__react_impl", "jit_scatter")


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.pumps <= 0:
        return None
    ns = tr.module_ns(lambda name: name in PROGRAMS)
    if ns <= 0:
        return None
    return ns / tr.pumps / 1e6
