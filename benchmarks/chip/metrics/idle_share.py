"""Share of the traced window in which no operation ran on the device."""


def read(run):
    red = run.reduced
    if not red or red["idle_share"] is None:
        return None
    return 100.0 * red["idle_share"]
