"""Host time of a window round in the program's round loop: the
``round[r]`` span less its fenced ``device`` span (data, ``host_sync``
and the loop itself), mean over the traced rounds, in ms."""


def read(ctx):
    rounds = [r for r in ctx.trace.rounds if "device" in r.spans]
    if not rounds:
        return None
    return 1e3 * sum(r.seconds - r.span_seconds("device")
                     for r in rounds) / len(rounds)
