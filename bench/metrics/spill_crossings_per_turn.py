"""spill_crossings_per_turn: evictions plus wakes (ServeLoop.counts)
over the turns due in the window."""


def read(run):
    turns = len(run.rec.turns_due)
    if not turns:
        return None
    return (run.counts["evicted"] + run.counts["woken"]) / turns
