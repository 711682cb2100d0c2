"""1 - union of the device's operation intervals over the traced slice."""


def read(facts):
    return 100.0 * (1.0 - facts["trace"].busy_s / facts["trace_window_s"])
