"""95th percentile of the gaps between successive tokens of the requests
that finished in the window, both tokens committed inside it (the engine's
per-request ``token_times``, on its clock)."""
import numpy as np


def read(rec):
    gaps = (rec.get("serve") or {}).get("token_gaps")
    if not gaps:
        return None
    return 1e3 * float(np.percentile(gaps, 95))
