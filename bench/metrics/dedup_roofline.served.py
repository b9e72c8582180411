"""dedup_roofline.served: the dedup election's share of its roofline, in
the cells that serve client updates. The reduction is in
bench/harness/readers.py."""
from harness.readers import dedup_roofline as read  # noqa: F401
