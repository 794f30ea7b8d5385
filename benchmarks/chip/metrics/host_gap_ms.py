"""host_gap_ms: mean over the window's working engine steps (outside the
traced part) of the host time around ``step()`` minus the program's own
seconds from dispatch to synced result of that step's jitted calls: the
engine's host work per step."""


def read(run):
    steps = run.busy_steps()
    if not steps:
        return None
    return sum(s.wall - s.program for s in steps) / len(steps) * 1e3
