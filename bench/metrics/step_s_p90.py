"""90th percentile of the window's step times, each step timed from the
previous step's outputs being ready to its own."""
import numpy as np


def read(run):
    return float(np.percentile(run.step_times, 90))
