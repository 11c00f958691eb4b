"""mfu_pct.open: ``mfu_pct`` (its reader) in an open-loop cell,
where it moves ``itl_p95_ms``."""
from harness import load_reader

read = load_reader("mfu_pct")
