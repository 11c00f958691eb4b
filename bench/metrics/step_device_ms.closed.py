"""step_device_ms.closed: ``step_device_ms`` (its reader) in a closed-loop
cell, where it moves ``itl_p95_ms.closed``."""
from harness import load_reader

read = load_reader("step_device_ms")
