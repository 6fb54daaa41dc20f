"""Steps of the sweep: its T-factor preamble schedule plus its main
schedule (`chipbench.steps`).  On the host path this cell drives, the
device runs the main schedule and the host the preamble; the preamble's
steps are counted as the device pipeline schedules them, so that moving
rows from one into the other does not change the count by itself."""


def read(ctx):
    return ctx["counters"].get("sweep_steps")
