"""Device busy time per training step in the traced slice: the union of the
device's operation intervals over the executions of the step's program (a
training slice runs one program of any size: the one that took most of the
device's time)."""


def read(facts):
    step = facts["trace"].program()
    if step is None:
        return None
    return 1e3 * facts["trace"].busy_s / step[1]
