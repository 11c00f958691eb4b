"""device_idle_pct.closed: ``device_idle_pct`` (its reader) in a closed-loop
cell, where it moves ``itl_p95_ms.closed``."""
from harness import load_reader

read = load_reader("device_idle_pct")
