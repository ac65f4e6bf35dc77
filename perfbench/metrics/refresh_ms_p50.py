"""api.session: median host time of a step that ran a refresh, from the
step's start through the result fetch after it (the benchmark's own
span), over the refreshes that started in the window."""
import numpy as np


def read(run):
    spans = [r.end - r.start for r in run.window.refreshes]
    return float(np.median(spans)) * 1e3 if spans else None
