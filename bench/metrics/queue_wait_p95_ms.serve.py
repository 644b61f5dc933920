"""queue_wait_p95_ms.serve: nearest-rank p95, over the requests due in the
window and served, of the wait from a request's due time to its
admission into an engine slot (`Completion.t_admit`)."""
import math


def read(r):
    v = r.job["readings"].get("queue_wait_p95_ms")
    return v if v is not None and math.isfinite(v) else None
