"""Shared arithmetic of the stage metrics: device milliseconds per wave slot."""


def stage_ms(ctx, program: str):
    """Device time of ``program``'s executions in the traced window, over the
    wave slots they carried; None where the program did not run there."""
    trace = ctx["trace"]
    prog = (trace or {}).get("programs", {}).get(program)
    if not prog or prog["count"] == 0 or prog["device_s"] <= 0:
        return None
    batch = ctx["cell"].traffic["service"]["batch"]
    return prog["device_s"] / (prog["count"] * batch) * 1e3
