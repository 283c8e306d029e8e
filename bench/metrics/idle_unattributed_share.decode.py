"""Share of the traced window's device idle time under no span of the
program (``xpk.*``): what the program's spans cannot name (none when the
trace holds no such span)."""

import program_spans


def read(ctx):
    if ctx["trace"] is None:
        return None
    path = program_spans.newest_trace()
    if path is None:
        return None
    idle = program_spans.idle_by_span(path)
    total = sum(idle.values())
    if set(idle) == {program_spans.UNATTRIBUTED} or total <= 0:
        return None
    return 100.0 * idle[program_spans.UNATTRIBUTED] / total
