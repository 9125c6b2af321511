"""95th percentile of how late the load generator took in a request:
the host time it was sent to the engine's queue minus its due time."""
import numpy as np


def read(run):
    late = run.record["lateness_s"]
    if not late:
        return None
    return 1e3 * float(np.percentile(late, 95))
