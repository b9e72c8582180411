"""cycles_per_s.storm: engine cycles a second on the served path, in
the cold-start cells (no client traffic). The reduction is in
bench/harness/readers.py."""
from harness.readers import cycles_per_s as read  # noqa: F401
