"""Share of the scanned steps in which traffic was in flight: the
program's own ``active_steps`` counter over the steps its fused scan ran,
summed over the window's points and over a fleet's members (the rest is
the drain tail)."""


def read(run):
    steps = sum(p.steps * len(p.members) for p in run.points)
    if not steps:
        return None
    return 100.0 * sum(p.active_steps for p in run.points) / steps
