"""Host time per sweep outside the engine's program: from a sweep's
start marker to the program's first op on the device (stacking, run
planning, config lowering, transfer, dispatch), and from the program's
last op to the next sweep's end marker (result transfer and unpacking),
both read from the device trace."""


def read(run):
    red = run["trace"]
    if not red:
        return None
    return 1000.0 * (red["front_s"] + red["back_s"])
