"""itl_p95_ms.closed: ``itl_p95_ms`` (its reader) in a closed-loop cell.
It has a bound of its own: a closed loop's gaps spread far less from run
to run than an open loop's, whose arrivals the seed orders."""
from harness import load_reader

read = load_reader("itl_p95_ms")
