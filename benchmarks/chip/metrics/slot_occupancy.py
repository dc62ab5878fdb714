"""Engine: mean share of the slots running per tick over the window
(ServingEngine.occupancy_stats), %."""


def read(run):
    return None if run.occupancy is None else 100.0 * run.occupancy
