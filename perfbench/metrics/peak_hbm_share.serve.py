"""peak_bytes_in_use of the fullest chip after the window, over its HBM."""


def read(facts):
    return 100.0 * facts["memory_peak_bytes"] / facts["peaks"]["hbm_bytes"]
