"""How late the load generator sent: 95th percentile of (sent - due), ms."""
import numpy as np


def read(run):
    late = [r["sent"] - r["due"] for r in run.get("requests", [])]
    return float(np.quantile(late, 0.95)) * 1e3 if late else None
