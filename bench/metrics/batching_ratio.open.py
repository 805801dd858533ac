"""Service layer (the micro-batching dispatcher): requests served per
device launch over the traced stretch of an open window, from the
dispatcher's counters read at both ends of the stretch."""


def read(ctx):
    if ctx.stretch_dispatch is None:
        return None
    s0, s1 = ctx.stretch_dispatch
    launches = s1["launches"] - s0["launches"]
    served = (s1["coalesced_requests"] - s0["coalesced_requests"]
              + s1["solo_launches"] - s0["solo_launches"])
    return served / launches if launches else None
