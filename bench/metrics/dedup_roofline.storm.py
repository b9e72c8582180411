"""dedup_roofline.storm: the dedup election's share of its roofline, in
the cold-start cells (no client traffic). The reduction is in
bench/harness/readers.py."""
from harness.readers import dedup_roofline as read  # noqa: F401
