"""tick_host_ms.closed: ``tick_host_ms`` (its reader) in a closed-loop
cell, where it moves ``itl_p95_ms.closed``."""
from harness import load_reader

read = load_reader("tick_host_ms")
