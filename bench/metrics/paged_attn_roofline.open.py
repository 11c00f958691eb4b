"""paged_attn_roofline.open: ``paged_attn_roofline`` (its reader) in an open-loop cell,
where it moves ``itl_p95_ms``."""
from harness import load_reader

read = load_reader("paged_attn_roofline")
